"""window_tokens_per_s.serve: output tokens whose step ended inside the
window, of every request, over the window's seconds. Below the knee this
is the offered load, more what the lead-in's requests carry into the
window and less what the window's carry out of it: with some 14 requests
in flight at either end it reads 145 to 183 with the order that the seed
draws (PR 26), so it is a record of collapse and holds no bound."""


def read(run):
    if "tokens_in_window" not in run.counters:
        return None
    return run.counters["tokens_in_window"] / run.window_s
