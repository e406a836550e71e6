"""ResNet-20 (CIFAR) / ResNet-18 (ImageNet) on the CIM convolution
framework — the paper's own evaluation architectures (Table II).

Every conv routes through the CIM conv forward (``repro.api.conv2d``:
stretched-kernel tiling + group conv + bit-split + column-wise W/psum
quantization). Following common CIM QAT practice (and the paper's
settings), the first conv and the final FC layer stay full-precision.
BatchNorm carries explicit running statistics in a separate ``state``
tree (functional; trainer threads it).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.cim_conv import _conv_forward, _init_conv
from repro.core.cim_linear import CIMConfig, _deprecated
from repro.obs import names as obs_names


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str
    depth: int                    # 20 (cifar) or 18 (imagenet-style)
    n_classes: int
    widths: Tuple[int, ...] = (16, 32, 64)
    in_hw: int = 32
    cim: CIMConfig = dataclasses.field(default_factory=CIMConfig)
    bn_momentum: float = 0.9

    @property
    def blocks_per_stage(self) -> int:
        if self.depth == 20:
            return 3
        return 2                  # resnet18: 2 basic blocks per stage


def _bn_init(c):
    return ({"scale": jnp.ones((c,), jnp.float32),
             "bias": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def _bn_apply(p, s, x, train: bool, momentum: float):
    xf = x.astype(jnp.float32)
    if train:
        mu = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.var(xf, axis=(0, 1, 2))
        new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mu,
                 "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mu, var, new_s = s["mean"], s["var"], s
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    return y.astype(x.dtype), new_s


def init(key: jax.Array, cfg: ResNetConfig):
    """Returns (params, bn_state)."""
    widths = cfg.widths if cfg.depth == 20 else (64, 128, 256, 512)
    nb = cfg.blocks_per_stage
    keys = iter(jax.random.split(key, 4 + 2 * len(widths) * nb * 2))
    params: Dict = {}
    state: Dict = {}
    c_in = 3
    # stem conv: full precision (standard CIM QAT practice)
    fp = cfg.cim.replace(enabled=False)
    params["stem"] = _init_conv(next(keys), 3, 3, c_in, widths[0], fp)
    params["stem_bn"], state["stem_bn"] = _bn_init(widths[0])
    c_in = widths[0]
    for si, w in enumerate(widths):
        for bi in range(nb):
            name = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            blk: Dict = {
                "conv1": _init_conv(next(keys), 3, 3, c_in, w, cfg.cim),
                "conv2": _init_conv(next(keys), 3, 3, w, w, cfg.cim),
            }
            bst: Dict = {}
            blk["bn1"], bst["bn1"] = _bn_init(w)
            blk["bn2"], bst["bn2"] = _bn_init(w)
            if stride != 1 or c_in != w:
                blk["proj"] = _init_conv(next(keys), 1, 1, c_in, w, cfg.cim)
                blk["bn_p"], bst["bn_p"] = _bn_init(w)
            params[name] = blk
            state[name] = bst
            c_in = w
    params["fc"] = {
        "w": (jax.random.normal(next(keys), (c_in, cfg.n_classes), jnp.float32)
              / jnp.sqrt(c_in)),
        "b": jnp.zeros((cfg.n_classes,), jnp.float32),
    }
    return params, state


def pack_deploy(params: Dict, cfg: ResNetConfig) -> Dict:
    """Deprecated: use ``repro.api.pack_model(params, cfg.cim)`` (or
    ``repro.api.model_artifact`` for a saveable ``DeployArtifact``). The
    generic tree walk packs every CIM conv to int digit planes; the
    full-precision stem, BN and FC pass through unchanged."""
    _deprecated("models.resnet.pack_deploy", "repro.api.pack_model")
    from repro.api import pack_model
    return pack_model(params, cfg.cim)


def conv_layer_names(cfg: ResNetConfig) -> Tuple[Tuple[str, int], ...]:
    """Ordered (layer_name, stride) pairs for every CIM conv in forward
    order — "s0b1.conv2", "s1b0.proj", ... The single source of layer
    identity shared by ``variation_keys``, ``forward(return_taps=True)``
    and the robustness harness's per-layer attribution."""
    widths = cfg.widths if cfg.depth == 20 else (64, 128, 256, 512)
    nb = cfg.blocks_per_stage
    out = []
    c_in = widths[0]
    for si, w in enumerate(widths):
        for bi in range(nb):
            name = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            out.append((f"{name}.conv1", stride))
            out.append((f"{name}.conv2", 1))
            if stride != 1 or c_in != w:
                out.append((f"{name}.proj", stride))
            c_in = w
    return tuple(out)


def variation_keys(key: Optional[jax.Array], cfg: ResNetConfig
                   ) -> Optional[Dict[str, jax.Array]]:
    """Per-layer variation keys, {layer_name: key}. ``forward`` consumes
    exactly these, so per-layer re-evaluation (error attribution) sees the
    same device noise as the end-to-end forward pass."""
    if key is None:
        return None
    names = [n for n, _ in conv_layer_names(cfg)]
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


def forward(params: Dict, state: Dict, x: jnp.ndarray, cfg: ResNetConfig,
            *, train: bool, variation_key: Optional[jax.Array] = None,
            variation_std=None, return_taps: bool = False):
    """x: (B, H, W, 3) -> (logits, new_bn_state).

    ``variation_key``/``variation_std`` evaluate one Monte-Carlo cell-
    noise realization (per-layer keys from ``variation_keys``; std may be
    a traced scalar so sigma sweeps don't recompile). With
    ``return_taps=True`` also returns {layer_name: conv input activation}
    — the hook the robustness harness uses for per-layer attribution.

    Each layer runs under a ``jax.named_scope`` (``obs.names``):
    ``resnet.stem``, ``resnet.s{i}b{j}`` with a nested scope per conv
    named by its parameter key, and ``resnet.head``.
    """
    widths = cfg.widths if cfg.depth == 20 else (64, 128, 256, 512)
    nb = cfg.blocks_per_stage
    new_state: Dict = {}
    taps: Dict[str, jnp.ndarray] = {}
    fp = cfg.cim.replace(enabled=False)
    with jax.named_scope(obs_names.SCOPE_RESNET_STEM):
        h = _conv_forward(x, params["stem"], fp, compute_dtype=jnp.float32)
        h, new_state["stem_bn"] = _bn_apply(
            params["stem_bn"], state["stem_bn"], h, train, cfg.bn_momentum)
        h = jax.nn.relu(h)
    vkeys = variation_keys(variation_key, cfg) or {}

    def conv(name, key, x_in, stride=1):
        if return_taps:
            taps[f"{name}.{key}"] = x_in
        with jax.named_scope(key):
            return _conv_forward(x_in, params[name][key], cfg.cim,
                                 stride=stride,
                                 variation_key=vkeys.get(f"{name}.{key}"),
                                 variation_std=variation_std,
                                 compute_dtype=jnp.float32)

    for si, w in enumerate(widths):
        for bi in range(nb):
            name = f"s{si}b{bi}"
            blk, bst = params[name], state[name]
            nst: Dict = {}
            stride = 2 if (bi == 0 and si > 0) else 1
            with jax.named_scope(obs_names.SCOPE_RESNET_BLOCK.format(
                    stage=si, block=bi)):
                y = conv(name, "conv1", h, stride)
                y, nst["bn1"] = _bn_apply(blk["bn1"], bst["bn1"], y, train,
                                          cfg.bn_momentum)
                y = jax.nn.relu(y)
                y = conv(name, "conv2", y)
                y, nst["bn2"] = _bn_apply(blk["bn2"], bst["bn2"], y, train,
                                          cfg.bn_momentum)
                if "proj" in blk:
                    sc = conv(name, "proj", h, stride)
                    sc, nst["bn_p"] = _bn_apply(blk["bn_p"], bst["bn_p"], sc,
                                                train, cfg.bn_momentum)
                else:
                    sc = h
                h = jax.nn.relu(y + sc)
            new_state[name] = nst
    with jax.named_scope(obs_names.SCOPE_RESNET_HEAD):
        h = jnp.mean(h, axis=(1, 2))
        logits = h @ params["fc"]["w"] + params["fc"]["b"]
    if return_taps:
        return logits, new_state, taps
    return logits, new_state


def calibrate(params: Dict, state: Dict, x: jnp.ndarray, cfg: ResNetConfig):
    """Run one forward pass, calibrating every CIM conv's s_a / s_p from
    the activations that actually reach it."""
    from repro.core.cim_conv import _calibrate_conv
    widths = cfg.widths if cfg.depth == 20 else (64, 128, 256, 512)
    nb = cfg.blocks_per_stage
    fp = cfg.cim.replace(enabled=False)
    p = {k: (dict(v) if isinstance(v, dict) else v) for k, v in params.items()}
    h = _conv_forward(x, p["stem"], fp, compute_dtype=jnp.float32)
    h, _ = _bn_apply(p["stem_bn"], state["stem_bn"], h, True, cfg.bn_momentum)
    h = jax.nn.relu(h)
    for si, w in enumerate(widths):
        for bi in range(nb):
            name = f"s{si}b{bi}"
            blk = dict(p[name])
            bst = state[name]
            stride = 2 if (bi == 0 and si > 0) else 1
            blk["conv1"] = _calibrate_conv(h, blk["conv1"], cfg.cim,
                                           stride=stride)
            y = _conv_forward(h, blk["conv1"], cfg.cim, stride=stride,
                              compute_dtype=jnp.float32)
            y, _ = _bn_apply(blk["bn1"], bst["bn1"], y, True, cfg.bn_momentum)
            y = jax.nn.relu(y)
            blk["conv2"] = _calibrate_conv(y, blk["conv2"], cfg.cim)
            y = _conv_forward(y, blk["conv2"], cfg.cim, compute_dtype=jnp.float32)
            y, _ = _bn_apply(blk["bn2"], bst["bn2"], y, True, cfg.bn_momentum)
            if "proj" in blk:
                blk["proj"] = _calibrate_conv(h, blk["proj"], cfg.cim,
                                              stride=stride)
                sc = _conv_forward(h, blk["proj"], cfg.cim, stride=stride,
                                   compute_dtype=jnp.float32)
                sc, _ = _bn_apply(blk["bn_p"], bst["bn_p"], sc, True,
                                  cfg.bn_momentum)
            else:
                sc = h
            h = jax.nn.relu(y + sc)
            p[name] = blk
    return p
