"""sched_host_share.serve: share of the window's wall time outside the
benchmark's spans around ``engine.prefill_step`` and ``engine.decode``:
the scheduler's and the generator's host work, and waiting for arrivals."""


def read(run):
    if "ticks" not in run.counters:
        return None
    lo, hi = run.window
    inside = sum(e - max(s, lo) for name in ("engine.prefill_step",
                                             "engine.decode")
                 for s, e in run.spans.within(name, lo, hi))
    return 100.0 * (1.0 - inside / run.window_s)
