"""queue_wait_p90_ms.serve: 90th percentile of the scheduler's
``admit_time`` less the time the request was due."""
from harness.core import percentile


def read(run):
    waits = run.counters.get("queue_wait_s")
    return 1e3 * percentile(waits, 0.9) if waits else None
