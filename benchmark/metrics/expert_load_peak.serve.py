"""expert_load_peak.serve: how unevenly a pass loads the experts: 90th
percentile (nearest rank), over the window's passes and their layers, of
the busiest expert's assignments over the mean over all experts."""
from harness.core import percentile


def read(run):
    passes = run.counters.get("passes")
    if not passes:
        return None
    return percentile([peak for p in passes for peak in p[3]], 0.9)
