"""Device idle share of the traced window (device trace). Moves ``img_s``."""
from perfbench import readers


def read(ctx):
    return readers.idle_share(ctx, "img_s")
