"""step_mfu.train: the whole step's share of the chips' peak: model FLOPs
per token (6 N + 12 L H S, no recomputation, no position table) times the
step's tokens over the median step's time (``step_ms.train``: the window's
rate would count the profiler's own start in a traced run), over chips
times the bf16 peak."""
from harness import core


def read(run):
    if run.peaks is None or "flops_per_token" not in run.counters:
        return None
    step_ms = core.load_module(run.find("metrics", "step_ms.train.py"))
    seconds = step_ms.read(run) / 1e3
    return 100.0 * run.counters["flops_per_token"] \
        * run.counters["tokens_per_step"] / seconds / (
            run.chips * run.peaks["bf16_flops_per_s"])
