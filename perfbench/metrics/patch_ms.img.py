"""Device milliseconds per unit in the CIM patch extraction and relayout:
ops under the program's ``cim.patches`` or ``cim.layout`` scopes
(scopes.py). Moves ``img_s``."""
from perfbench import scopes


def read(ctx):
    return scopes.patch_ms(ctx, "img_s",
                           lambda: scopes.unit_text("patch_ms.img"))
