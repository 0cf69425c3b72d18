"""paged_decode_roofline.serve: the paged decode kernel's share of its
roofline over the traced window (``kernels/paged_decode.py``): bytes are
the live K/V the algorithm needs, whatever the kernel walks."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "paged_decode.py"))
    return roofline.share(run, kernel)
