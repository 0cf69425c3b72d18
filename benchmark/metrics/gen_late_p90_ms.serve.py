"""gen_late_p90_ms.serve: how late the load generator ran: 90th
percentile of submit time less due time. A starved generator would
otherwise read as a fast server."""
from harness.core import percentile


def read(run):
    late = run.counters.get("gen_late_s")
    return 1e3 * percentile(late, 0.9) if late else None
