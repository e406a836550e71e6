"""Model FLOPs over the traced window, per cent of the int8 peak. Moves ``img_s``."""
from perfbench import readers


def read(ctx):
    return readers.mfu(ctx, "img_s")
