"""block_attention_roofline.serve: the share of its roofline that the
paged decode kernel reaches when a block engine feeds it whole blocks
(``kernels/block_attention.py``), over the traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "block_attention.py"))
    return roofline.share(run, kernel)
