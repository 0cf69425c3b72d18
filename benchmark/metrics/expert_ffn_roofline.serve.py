"""expert_ffn_roofline.serve: the grouped expert product's share of its
roofline over the traced window (``kernels/expert_ffn.py``): operations
by assignment, bytes the weights of the experts that got a token."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "expert_ffn.py"))
    return roofline.share(run, kernel)
