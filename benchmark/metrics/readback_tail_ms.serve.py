"""readback_tail_ms.serve: what the host does after the device finished a
decode program: median over ``engine.decode`` spans of their end less the
end of the ``engine.device_wait`` in their readback (the copy to the
host, the result unpacked, the per-sequence updates)."""
from harness import host_gaps, program_spans as ps


def read(run):
    acc = host_gaps.account(run)
    return None if acc is None else ps.median_ms(acc.tails)
