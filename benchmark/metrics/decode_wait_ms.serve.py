"""decode_wait_ms.serve: how long the host waits for one decode program:
median ``engine.readback`` under ``engine.decode`` where the engine's
``in_flight`` counter reads 1, that is where no chunk program had been
dispatched since the readback before."""
from harness import program_spans as ps


def read(run):
    spans = ps.traced(run)
    kids = ps.children(spans)
    return ps.median_ms(
        ps.ms(c) for s in ps.named(spans, "engine.decode")
        for c in kids.get(s.span_id, ())
        if c.name == "engine.readback" and c.attrs.get("in_flight") == 1)
