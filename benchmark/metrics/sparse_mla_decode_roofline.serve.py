"""sparse_mla_decode_roofline.serve: the share of its roofline that a
tick's latent attention over each sequence's selected rows
(``kernels/sparse_mla_decode.py``) reaches over the traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "sparse_mla_decode.py"))
    return roofline.share(run, kernel)
