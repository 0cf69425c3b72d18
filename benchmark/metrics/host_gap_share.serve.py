"""host_gap_share.serve: the gaps of ``harness/host_gaps.py`` summed over
the traced window, in percent: the device's idle share less arrivals, on
the program's own clock."""
from harness import host_gaps


def read(run):
    acc = host_gaps.account(run)
    if acc is None:
        return None
    return 100.0 * sum(g.hi - g.lo for g in acc.gaps) / acc.window_ns
