"""CIM kernels' share of their roofline (device trace, roofline.py). Moves ``img_s``."""
from perfbench import readers


def read(ctx):
    return readers.cim_roofline(ctx, "img_s")
