"""ttft_mean_ms.serve: mean, over every request due inside the window, of
the first token's time less the time the request was DUE. A request that
failed or was refused waited to the drain limit."""
import statistics


def read(run):
    ttft = run.counters.get("ttft_s")
    return 1e3 * statistics.fmean(ttft) if ttft else None
