"""The compiled text of the cell's unit of work, for ``scopes.py``.

``kimi-vl-a3b-moonvit.py`` jits the deploy ``vit.forward`` over the
packed artifact (``jitted_forward``); this lowers the same function for
abstract arguments of the same shapes and dtypes (the packed tree from
``jax.eval_shape`` of the pack, the images of one pack of the traffic)
and compiles it. After a run's warm-up the compile is a load from the
persistent compilation cache, and the text names every instruction as
the trace of that run does. Called under the matmul precision the
configuration states, as the harness calls everything.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench import loader

CELL = loader.sibling(__file__, "kimi-vl-a3b-moonvit.py")


def _specs(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def compiled_text(sz, mix) -> str:
    from repro.api import model_artifact
    from repro.models import vit
    from repro.nn.module import init_params
    cfg = CELL.model_config(sz)
    params = jax.eval_shape(lambda k: init_params(vit.specs(cfg), k),
                            jax.random.PRNGKey(0))
    served = []

    def pack(p):
        art = model_artifact(p, cfg.cim, meta={"arch": sz["name"]})
        served.append(art.config)
        return art.params
    packed = jax.eval_shape(pack, params)
    forward = CELL.jitted_forward(cfg.replace(cim=served[0]))
    images = [jax.ShapeDtypeStruct((h, w, 3), jnp.float32)
              for h, w in CELL.shapes(mix)]
    return forward.lower(_specs(packed), images).compile().as_text()
