"""sparse_mla_prefill_roofline.serve: the share of its roofline that a
chunk's latent attention over each query's selected rows
(``kernels/sparse_mla_prefill.py``) reaches over the traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "sparse_mla_prefill.py"))
    return roofline.share(run, kernel)
