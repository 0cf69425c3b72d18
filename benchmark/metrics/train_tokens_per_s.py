"""train_tokens_per_s: all tokens of all steps of the window, over the
whole mesh, over the window's seconds. The window ends with the step that
passes ``--seconds``; every step ends in a loss readback."""


def read(run):
    if "tokens" not in run.counters:
        return None
    return run.counters["tokens"] / run.window_s
