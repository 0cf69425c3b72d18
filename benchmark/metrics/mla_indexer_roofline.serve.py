"""mla_indexer_roofline.serve: the share of its roofline that the indexer's
score over the index-key pages (decode and prefill calls, each its own
kind: ``kernels/mla_indexer.py``) reaches over the traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "mla_indexer.py"))
    return roofline.share(run, kernel)
