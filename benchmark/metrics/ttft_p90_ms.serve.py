"""ttft_p90_ms.serve: 90th percentile (nearest rank), over every request
due inside the window, of the first token's time less the time the request
was DUE. A request that failed or was refused waited to the drain limit.
With ninety requests a window it is the ninth longest wait, and the
chunks of the few long prompts are served in turn ahead of whoever comes
next: where the seed's order puts them moved it from 759 to 2,528 ms (and
the mean from 397 to 890) on seven seeds (PR 26), so no bound holds it."""
from harness.core import percentile


def read(run):
    ttft = run.counters.get("ttft_s")
    return 1e3 * percentile(ttft, 0.9) if ttft else None
