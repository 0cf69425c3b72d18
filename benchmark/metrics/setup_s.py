"""setup_s: process start to window open (import, weights, placement,
compile or cache load, warm-up, the first compared steps or the lead-in)."""


def read(run):
    return run.setup_s
