"""prefill_chunk_ms.serve: one chunk program prepared, run and read back:
median whole duration of the ``engine.prefill_step`` spans of a prompt's
last chunk that were entered with nothing in flight (``in_flight`` 0), so
that the readback waits for this chunk alone."""
from harness import program_spans as ps


def read(run):
    return ps.median_ms(
        ps.ms(s) for s in ps.named(ps.traced(run), "engine.prefill_step")
        if s.attrs.get("final") and s.attrs.get("in_flight") == 0)
