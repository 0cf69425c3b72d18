"""cache_bytes_per_token.serve: what a live token costs in cache and
state: median, over the ``engine.decode`` spans of the traced window, of
``cache_bytes`` (the bytes of the pages in use and of the state slots in
use) over ``live_ctx`` (the sum of the live lengths). A model whose every
layer is attention over the whole context would read its K/V bytes a
token here whatever the lengths (163,840 at these widths and 32 layers)."""
from harness import program_spans as ps


def read(run):
    return ps.median_ms(
        s.attrs["cache_bytes"] / s.attrs["live_ctx"]
        for s in ps.named(ps.traced(run), "engine.decode")
        if s.attrs.get("live_ctx"))
