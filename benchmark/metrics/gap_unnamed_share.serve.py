"""gap_unnamed_share.serve: the part of the gaps of ``harness/host_gaps.py``
that no program leaf span names (a phase container's own time between
its children, or no program span at all; ``gc`` names its time), in percent
of their sum: what the program's spans leave unexplained."""
from harness import host_gaps


def read(run):
    acc = host_gaps.account(run)
    total = 0 if acc is None else sum(g.hi - g.lo for g in acc.gaps)
    if not total:
        return None
    return 100.0 * (total - sum(g.named_ns for g in acc.gaps)) / total
