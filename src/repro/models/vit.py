"""MoonViT, the native-resolution vision tower of Kimi-VL
(arXiv:2504.07491).

Images enter at their own resolution. Each (H, W, 3) image is cut into
p x p patches, row-major, each flattened (row, column, channel); the
patches of every image of a call are packed into one sequence:

1. patch embedding, a float linear (p*p*3 -> d) plus a bias, and the
   learned ``pos_grid`` x ``pos_grid`` position table resized to the
   image's (h, w) patch grid (bicubic, a = -0.75, half-pixel centres:
   the published code's ``F.interpolate(mode="bicubic")``);
2. ``n_layers`` pre-norm blocks over the whole pack, under ``lax.scan``:
   x += Wo Attn(RoPE2D(q), RoPE2D(k), v) on LN1(x), with q, k, v from one
   fused linear, then x += W2 GELU_tanh(W1 LN2(x)). Attention is
   bidirectional and stays inside each image: a run of neighbouring
   images of one grid shares one batched ``layers.attention`` call, and
   no mask spans two images, so attention covers sum n_i^2 query-key
   pairs, not (sum n_i)^2;
3. the final LayerNorm, then the projector: a LayerNorm per patch, the
   ``merge`` x ``merge`` merge into one (merge^2 d)-wide token, Linear,
   GELU, Linear to ``out_dim``.

Every linear but the patch embedding is a CIM linear (``apply_linear``),
so a packed config runs each on the deploy kernels. The patch embedding
stays float, like the ResNet stem: its 588-row tap set is no array's
stretched kernel. The float matmuls (patch embedding, position resize,
QK and AV) run at the caller's default matmul precision.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.nn.linear import apply_linear, linear_specs
from repro.nn.module import ParamSpec, stack_specs
from repro.obs import names
from .layers import (apply_mlp, apply_norm, attention, cdt, mlp_specs,
                     norm_specs, pdt, rope_2d)


def specs(cfg: ModelConfig) -> Dict:
    v, d, dt = cfg.vision, cfg.d_model, pdt(cfg)
    wide = d * v.merge ** 2
    block = {
        "ln1": norm_specs(cfg),
        "wqkv": linear_specs(d, 3 * d, cim=cfg.cim, in_axis="embed",
                             out_axis="heads", dtype=dt),
        "wo": linear_specs(d, d, cim=cfg.cim, in_axis="heads",
                           out_axis="embed", dtype=dt),
        "ln2": norm_specs(cfg),
        "mlp": mlp_specs(cfg),
    }
    return {
        "patch": {"w": ParamSpec((3 * cfg.patch_size ** 2, d), dt,
                                 "fan_in:1.0", (None, "embed")),
                  "b": ParamSpec((d,), dt, "zeros", ("embed",))},
        "pos": ParamSpec((v.pos_grid, v.pos_grid, d), dt, "normal:0.02",
                         (None, None, "embed")),
        "layers": stack_specs(block, cfg.n_layers),
        "ln_f": norm_specs(cfg),
        "proj_ln": norm_specs(cfg),
        "proj1": linear_specs(wide, wide, cim=cfg.cim, out_axis="mlp",
                              dtype=dt),
        "proj2": linear_specs(wide, v.out_dim, cim=cfg.cim, in_axis="mlp",
                              dtype=dt),
    }


def grid(shape: Sequence[int], cfg: ModelConfig) -> Tuple[int, int]:
    """The (h, w) patch grid of an (H, W, 3) image; each side must be a
    whole number of merged patches."""
    side = cfg.patch_size * cfg.vision.merge
    if shape[0] % side or shape[1] % side or shape[-1] != 3:
        raise ValueError(f"image of shape {tuple(shape)}: height and width "
                         f"must be multiples of {side}, with 3 channels")
    return shape[0] // cfg.patch_size, shape[1] // cfg.patch_size


def bicubic(n_out: int, n_in: int, a: float = -0.75) -> np.ndarray:
    """(n_out, n_in) float32 weights of a bicubic resize with half-pixel
    centres and clamped borders (torch's ``align_corners=False``)."""
    x = (np.arange(n_out) + 0.5) * (n_in / n_out) - 0.5
    x0 = np.floor(x)
    t = x - x0
    u = 1.0 - t
    w = [((a * (t + 1) - 5 * a) * (t + 1) + 8 * a) * (t + 1) - 4 * a,
         ((a + 2) * t - (a + 3)) * t * t + 1,
         ((a + 2) * u - (a + 3)) * u * u + 1,
         ((a * (u + 1) - 5 * a) * (u + 1) + 8 * a) * (u + 1) - 4 * a]
    m = np.zeros((n_out, n_in))
    for j, wj in enumerate(w):
        idx = np.clip(x0 + j - 1, 0, n_in - 1).astype(int)
        np.add.at(m, (np.arange(n_out), idx), wj)
    return m.astype(np.float32)


def _patches(img: jnp.ndarray, p: int) -> jnp.ndarray:
    hh, ww = img.shape[0] // p, img.shape[1] // p
    x = img.reshape(hh, p, ww, p, img.shape[-1]).transpose(0, 2, 1, 3, 4)
    return x.reshape(hh * ww, -1)


def _merge(x: jnp.ndarray, h: int, w: int, m: int) -> jnp.ndarray:
    """(h*w, d) patch tokens of one image -> (h/m * w/m, m*m*d)."""
    d = x.shape[-1]
    x = x.reshape(h // m, m, w // m, m, d).transpose(0, 2, 1, 3, 4)
    return x.reshape((h // m) * (w // m), m * m * d)


def _runs(grids: List[Tuple[int, int]]) -> List[Tuple[int, int, int]]:
    """(first token, images, tokens per image) of each run of neighbouring
    images that share a grid."""
    runs, at, last = [], 0, None
    for g in grids:
        if g == last:
            runs[-1][1] += 1
        else:
            runs.append([at, 1, g[0] * g[1]])
        last = g
        at += g[0] * g[1]
    return [tuple(r) for r in runs]


def _attend(p: Dict, x: jnp.ndarray, cfg: ModelConfig, runs, rows,
            cols) -> jnp.ndarray:
    """The fused q, k, v linear and the attention core, up to the output
    linear's input."""
    n = x.shape[0]
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    qkv = apply_linear(p["wqkv"], x, cfg.cim, compute_dtype=cdt(cfg))
    with jax.named_scope(names.SCOPE_VIT_ATTN):
        qkv = qkv.reshape(n, 3, h, hd)
        q = rope_2d(qkv[:, 0], rows, cols, cfg.rope_theta)
        k = rope_2d(qkv[:, 1], rows, cols, cfg.rope_theta)
        v = qkv[:, 2]
        out = []
        for start, count, size in runs:
            def run(a):
                return a[start:start + count * size].reshape(count, size, h,
                                                             hd)
            o = attention(run(q), run(k), run(v), causal=False,
                          chunk=cfg.attn_chunk)
            out.append(o.reshape(count * size, h * hd))
        return jnp.concatenate(out) if len(out) > 1 else out[0]


def forward(params: Dict, images: Sequence[jnp.ndarray], cfg: ModelConfig,
            return_taps: bool = False):
    """``images``: (H_i, W_i, 3) arrays (``grid`` says which sizes) ->
    for each image its (h_i w_i / merge^2, out_dim) projector tokens.
    Tracing it records the attention's query-key pairs, sum n_i^2, as
    the ``vit.attn.pairs`` scalar (``repro.obs.compiles`` counts it).

    With ``return_taps`` also the pack's (sum n_i, d) tokens between the
    stages, so each stage can be checked on the program's own input:
    ``(tokens, taps)`` with ``taps["embed"]`` the patch tokens entering
    the first block and ``taps["blocks"]`` a dict of (n_layers, sum n_i,
    d) arrays, per block: ``attn`` the attention core's output (the
    output linear's input), ``mid`` the residual after the attention
    half, ``out`` the block's output."""
    v, p, dt = cfg.vision, cfg.patch_size, cdt(cfg)
    grids = [grid(im.shape, cfg) for im in images]
    with jax.named_scope(names.SCOPE_VIT_PATCH_EMBED):
        x = jnp.concatenate([_patches(im.astype(dt), p) for im in images])
        x = (jnp.dot(x, params["patch"]["w"].astype(dt))
             + params["patch"]["b"].astype(dt))
        table = params["pos"].astype(dt)
        x = x + jnp.concatenate([
            jnp.einsum("hH,HWd,wW->hwd", bicubic(h, v.pos_grid), table,
                       bicubic(w, v.pos_grid)).reshape(h * w, -1)
            for h, w in grids])
    rows = jnp.asarray(np.concatenate([np.repeat(np.arange(h), w)
                                       for h, w in grids]))
    cols = jnp.asarray(np.concatenate([np.tile(np.arange(w), h)
                                       for h, w in grids]))
    runs = _runs(grids)
    jax.monitoring.record_scalar(names.VIT_ATTN_PAIRS,
                                 sum(n * n * c for _, c, n in runs))

    def block(x, lp):
        with jax.named_scope(names.SCOPE_VIT_BLOCK):
            att = _attend(lp, apply_norm(lp["ln1"], x, cfg), cfg, runs,
                          rows, cols)
            mid = x + apply_linear(lp["wo"], att, cfg.cim, compute_dtype=dt)
            x = mid + apply_mlp(lp["mlp"], apply_norm(lp["ln2"], mid, cfg),
                                cfg)
            return x, ({"attn": att, "mid": mid, "out": x} if return_taps
                       else None)
    x0 = x
    x, outs = jax.lax.scan(block, x, params["layers"])
    x = apply_norm(params["proj_ln"], apply_norm(params["ln_f"], x, cfg), cfg)
    with jax.named_scope(names.SCOPE_VIT_MERGE):
        merged, at = [], 0
        for h, w in grids:
            merged.append(_merge(x[at:at + h * w], h, w, v.merge))
            at += h * w
        x = jnp.concatenate(merged)
    x = jax.nn.gelu(apply_linear(params["proj1"], x, cfg.cim,
                                 compute_dtype=dt), approximate=False)
    x = apply_linear(params["proj2"], x, cfg.cim, compute_dtype=dt)
    out, at = [], 0
    for h, w in grids:
        n = (h // v.merge) * (w // v.merge)
        out.append(x[at:at + n])
        at += n
    if return_taps:
        return out, {"embed": x0, "blocks": outs}
    return out
