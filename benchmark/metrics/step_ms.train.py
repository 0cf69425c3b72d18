"""step_ms.train: median over the window's steps of feed + step +
readback on the host clock."""
import statistics


def read(run):
    lo, hi = run.window
    ends = [e for _, e in run.spans.within("readback", lo, hi)]
    starts = [s for s, e in run.spans.within("train.feed", lo, hi)]
    steps = [e - s for s, e in zip(starts, ends)]
    return 1e3 * statistics.median(steps) if steps else None
