"""The compiled text of the cell's unit of work, for ``scopes.py``.

``resnet18-imagenet.py`` jits the deploy ``resnet.forward`` over the
packed artifact; this lowers the same function for abstract arguments of
the same shapes and dtypes (the packed tree from ``jax.eval_shape`` of
the pack, the images of the traffic's batch) and compiles it. After a
run's warm-up the compile is a load from the persistent compilation
cache, and the text names every instruction as the trace of that run
does. Called under the matmul precision the configuration states, as
the harness calls everything.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from perfbench import loader

CELL = loader.sibling(__file__, "resnet18-imagenet.py")


def _specs(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def compiled_text(sz, mix) -> str:
    from repro.api import model_artifact
    from repro.models import resnet
    rcfg = CELL.resnet_config(sz, CELL.cim_config(sz))
    params, state = jax.eval_shape(lambda k: resnet.init(k, rcfg),
                                   jax.random.PRNGKey(0))
    served = []

    def pack(p):
        art = model_artifact(p, rcfg.cim, meta={"arch": sz["name"]})
        served.append(art.config)
        return art.params
    packed = jax.eval_shape(pack, params)
    serve_cfg = dataclasses.replace(rcfg, cim=served[0])
    forward = jax.jit(lambda p, s, x: resnet.forward(
        p, s, x, serve_cfg, train=False)[0])
    x = jax.ShapeDtypeStruct((mix["batch"], mix["hw"], mix["hw"], 3),
                             jnp.float32)
    return forward.lower(_specs(packed), _specs(state), x).compile().as_text()
