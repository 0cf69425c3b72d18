"""selective_scan_chunk_roofline.serve: the share of its roofline that the
prefill chunks' selective scan reaches (``kernels/selective_scan_chunk.py``),
over the traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels",
                                       "selective_scan_chunk.py"))
    return roofline.share(run, kernel)
