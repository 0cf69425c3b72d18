"""passes_per_token.serve: passes of both kinds that the window's
sequences went through (a pass over n sequences counts n) over the
tokens that came out inside the window. A block of 4 that unmasks one
position a pass and then commits reads 1.25."""


def read(run):
    passes = run.counters.get("passes")
    if not passes or not run.counters.get("tokens_in_window"):
        return None
    return sum(p[1] for p in passes) / run.counters["tokens_in_window"]
