"""gap_sched_ms.serve: the scheduler's part of a gap: median over the gaps
of ``harness/host_gaps.py`` of the time that the scheduler's leaf spans
name (``sched.*``, ``pool.*``, ``prefix.*``, ``state.*``; the phase
containers' own time is not among it)."""
from harness import host_gaps, program_spans as ps


def read(run):
    acc = host_gaps.account(run)
    return None if acc is None else ps.median_ms(
        g.sched_ns / 1e6 for g in acc.gaps)
