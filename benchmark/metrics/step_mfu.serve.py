"""step_mfu.serve: the whole engine's share of the chip's peak: model
FLOPs (2 N + 4 L H context) of every prompt token a prefill chunk
processed and every token a decode tick produced inside the window, over
the window's seconds and the bf16 peak."""


def read(run):
    if run.peaks is None or "ticks" not in run.counters:
        return None
    ctx = [at + i + 1 for _, at, n in run.counters["chunks"]
           for i in range(n)]
    ctx += [n for _, lens, _ in run.counters["ticks"] for n in lens]
    flops = run.model.serve_flops(run.config, ctx)
    return 100.0 * flops / run.window_s / (
        run.chips * run.peaks["bf16_flops_per_s"])
