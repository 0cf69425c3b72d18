"""prefill_host_ms.serve: the host's part of a prefill chunk: median over
the program's ``engine.prefill_step`` spans of the traced window of the
``engine.host_prep`` and ``engine.dispatch`` under them."""
from harness import program_spans as ps


def read(run):
    spans = ps.traced(run)
    kids = ps.children(spans)
    return ps.median_ms(
        ps.child_ms(s, kids, "engine.host_prep", "engine.dispatch")
        for s in ps.named(spans, "engine.prefill_step"))
