"""shared_kv_decode_roofline.serve: the share of its roofline that the
paged decode kernel reaches over the shared pages (one layer's K/V, read
by the full layer and every cross layer of a tick:
``kernels/shared_kv_decode.py``), over the traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "shared_kv_decode.py"))
    return roofline.share(run, kernel)
