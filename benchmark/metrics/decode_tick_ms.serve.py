"""decode_tick_ms.serve: median of the scheduler's own ``step_times``
over the window: the decode call alone, admission and prefill excluded."""
import statistics


def read(run):
    ticks = run.counters.get("decode_step_s")
    return 1e3 * statistics.median(ticks) if ticks else None
