"""pool_peak_share.serve: the most tokens that live sequences held in the
pool after any step of the window, over the pool's capacity in tokens.
(``pages_in_use`` would not do: with the prefix cache on it also counts
the pages that the cache retains, and stays near 100%.)"""


def read(run):
    if "live_tokens_peak" not in run.counters:
        return None
    return 100.0 * run.counters["live_tokens_peak"] \
        / run.counters["pool"]["capacity_tokens"]
