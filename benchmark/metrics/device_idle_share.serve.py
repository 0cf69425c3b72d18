"""device_idle_share.serve: 1 less the union of the device's operation
intervals over the traced window, mean over the chips."""
from harness import trace


def read(run):
    if run.trace_summary is None:
        return None
    return trace.idle_share(run.trace_summary)
