"""denoise_pass_ms.serve: one pass over the running sequences' blocks,
prepared, run and read back: median whole duration of the program's
``engine.decode`` spans of the traced window whose ``commit`` is 0 (not
every sequence of the pass was committing) and whose readback waited for
this program alone (``in_flight`` 1)."""
from harness import program_spans as ps


def read(run):
    spans = ps.traced(run)
    kids = ps.children(spans)
    return ps.median_ms(
        ps.ms(s) for s in ps.named(spans, "engine.decode")
        if s.attrs.get("commit") == 0 and any(
            c.name == "engine.readback" and c.attrs.get("in_flight") == 1
            for c in kids.get(s.span_id, ())))
