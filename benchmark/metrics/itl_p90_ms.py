"""itl_p90_ms: 90th percentile (nearest rank) over all gaps between
consecutive output tokens of the finished requests due inside the window,
on the wall clock read after each ``sched.step()``."""
from harness.core import percentile


def read(run):
    gaps = run.counters.get("itl_s")
    return 1e3 * percentile(gaps, 0.9) if gaps else None
