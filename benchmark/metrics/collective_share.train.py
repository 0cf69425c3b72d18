"""collective_share.train: share of the traced window in which a
chip runs a collective operation (all-reduce, all-gather, reduce-scatter,
collective-permute) as its own time, mean over the chips. Operations on a
chip's line run one at a time, so a collective's self time is time in
which no compute runs there; what the asynchronous line overlaps with it
is not subtracted, so this is the collectives' cost, not their exposed
part alone."""


def read(run):
    t = run.trace_summary
    if t is None or run.chips < 2:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
