"""sched_self_ms.serve: the scheduler's own host time in a tick: median
over the program's ``sched.step`` spans of the traced window of their
duration less what the ``engine.*`` spans under them cover."""
from harness import program_spans as ps


def read(run):
    spans = ps.traced(run)
    kids = ps.children(spans)
    return ps.median_ms(ps.ms(s) - ps.covered_ms(s, kids, "engine.")
                        for s in ps.named(spans, "sched.step"))
