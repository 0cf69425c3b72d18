"""held_expert_ffn_roofline.serve: the share of its roofline that the
grouped product of the experts this chip holds, by the program's count
of their assignments (``kernels/held_expert_ffn.py``) reaches over the
traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "held_expert_ffn.py"))
    return roofline.share(run, kernel)
