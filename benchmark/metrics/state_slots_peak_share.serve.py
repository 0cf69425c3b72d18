"""state_slots_peak_share.serve: the most state slots in use on any
``engine.decode`` span of the traced window (its ``state_slots``), over
the slots the deployment has (its widest decode bucket)."""
from harness import program_spans as ps


def read(run):
    used = [s.attrs["state_slots"] for s in ps.named(ps.traced(run),
                                                     "engine.decode")
            if "state_slots" in s.attrs]
    if not used:
        return None
    return 100.0 * max(used) / max(run.traffic["decode_buckets"])
