"""window_decode_roofline.serve: the share of its roofline that the paged
decode kernel reaches over the window layers' rings (a sequence's last
``sliding_window`` rows a window layer, one call a layer a tick:
``kernels/window_decode.py``), over the traced window."""
from harness import core, roofline


def read(run):
    kernel = core.load_module(run.find("kernels", "window_decode.py"))
    return roofline.share(run, kernel)
