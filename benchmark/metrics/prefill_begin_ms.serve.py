"""prefill_begin_ms.serve: median of the program's ``engine.prefill_begin``
spans of the traced window: the prefix-cache match and the pages of one
new prompt."""
from harness import program_spans as ps


def read(run):
    return ps.median_ms(ps.ms(s) for s in ps.named(
        ps.traced(run), "engine.prefill_begin"))
