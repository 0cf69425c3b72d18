"""flash_attn_roofline.train: the flash-attention kernels' share of their
roofline over the traced window, forward and both backward kernels
together: the least time the chip could take for the calls the trace
shows (``kernels/flash_attn.py``) over the time it took."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "flash_attn.py"))
    return roofline.share(run, kernel)
