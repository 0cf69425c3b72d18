"""host_gap_ms.serve: median gap, the device without a program while the
host works between its programs: from the end of an
``engine.device_wait`` to the end of the next ``engine.dispatch`` entered
with nothing in flight, stretches that overlap ``wait_for_arrival`` left
out (``harness/host_gaps.py``)."""
from harness import host_gaps, program_spans as ps


def read(run):
    acc = host_gaps.account(run)
    return None if acc is None else ps.median_ms(
        host_gaps.gap_ms(g) for g in acc.gaps)
