"""decode_host_ms.serve: the host's part of a decode call: median over
the program's ``engine.decode`` spans of the traced window of their
duration less the ``engine.readback`` under them (preparing the inputs,
handing the program to the device)."""
from harness import program_spans as ps


def read(run):
    spans = ps.traced(run)
    kids = ps.children(spans)
    return ps.median_ms(ps.ms(s) - ps.child_ms(s, kids, "engine.readback")
                        for s in ps.named(spans, "engine.decode"))
