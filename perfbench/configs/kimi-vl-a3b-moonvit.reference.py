"""Plain reference for MoonViT, the vision tower of Kimi-VL-A3B, under the
paper's CIM quantization.

Straightforward ``jax.numpy`` in float32 at HIGHEST precision, written
from the published description, one image at a time; it imports nothing
of the program:

- MoonViT (Kimi-VL technical report, arXiv:2504.07491, and the modeling
  code published with moonshotai/Kimi-VL-A3B-Instruct): the image is cut
  into p x p patches, row-major, each flattened (row, column, channel);
  a linear (3 p^2 -> d) plus a bias embeds them, and the learned
  ``init_pos_emb_height`` x ``init_pos_emb_width`` position table,
  resized bicubically (a = -0.75, half-pixel centres, clamped borders)
  to the image's patch grid, is added. Then pre-norm blocks:
  x += Wo Attn(RoPE2D(q), RoPE2D(k), v) on LN(x), q, k, v from one fused
  linear, bidirectional over the image's patches, scaled by
  1/sqrt(head_dim); x += W2 GELU_tanh(W1 LN(x)). 2-D RoPE turns each
  head's adjacent coordinate pairs (2j, 2j+1): pair 2i by the patch's
  column times w_i, pair 2i+1 by its row times w_i, w_i =
  1 / theta^(4i / head_dim). A final LayerNorm; then the projector:
  LayerNorm per patch, each 2 x 2 patch neighbourhood merged into one
  token (row-major within it), Linear, GELU (erf), Linear to the language
  model's width. LayerNorm weights are 1 and biases 0, and the linears
  but the patch embedding carry no bias (see the config's ``assumed``).
- The paper's CIM linear (arXiv:2502.07842, section III-A): the K input
  rows are cut into arrays of ``array_rows``; activations are
  ``act_bits`` signed codes with one scale per linear, weights
  ``weight_bits`` signed codes with one scale per (array tile, column),
  split sign-magnitude over ``cell_bits`` cells; every (bit split, array
  tile, column) partial sum is quantized by a ``psum_bits`` ADC with its
  own scale, dequantized by ``2^(cell_bits*split) * s_w * s_a`` and added
  up. The patch embedding stays float.

The weights are made here from the seed; ``calibrate`` sets every CIM
linear's ``s_a`` and ``s_p`` (float32, not powers of two) to a few
standard deviations over ``q_p`` of what reaches them in one image.
``embeddings`` computes one image, its linears and attention queries in
blocks of rows, so that the largest image fits one chip; ``embed``,
``block_stages`` and ``project`` compute its stages, each on a given
input, for a comparison that hands each stage the program's
own input. The control computes the same with every activation rounded
through bfloat16, the step below the float32 that the configuration
states.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DTYPES = {None: None, "bfloat16": jnp.bfloat16}
#: rows of a linear's input, and attention queries, taken at a time
BLOCK = 1024
#: the CIM linears of one block, (name, K, N) as functions of (d, ff)
BLOCK_LINEARS = (("wqkv", 1, 3), ("wo", 1, 1), ("wu", 1, "ff"),
                 ("wd", "ff", 1))


def _rnd(x, dt):
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def qrange(bits):
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def n_split(cim):
    return math.ceil(cim["weight_bits"] / cim["cell_bits"])


def vision(sz):
    """The tower's sizes under short names."""
    v = sz["vision_config"]
    d, heads = v["hidden_size"], v["num_attention_heads"]
    mh, mw = v["merge_kernel_size"]
    if mh != mw or v["init_pos_emb_height"] != v["init_pos_emb_width"]:
        raise ValueError("square merge kernels and position tables only")
    return {"d": d, "heads": heads, "hd": d // heads,
            "ff": v["intermediate_size"], "layers": v["num_hidden_layers"],
            "patch": v["patch_size"], "pos": v["init_pos_emb_height"],
            "merge": mh, "out": sz["text_config"]["hidden_size"],
            "theta": v["rope_theta"], "eps": v["layer_norm_eps"]}


def linear_shapes(sz):
    """{name: (K, N)} of every CIM linear: those of one block (stacked
    over the layers) and the projector's."""
    vz = vision(sz)
    size = {1: vz["d"], 3: 3 * vz["d"], "ff": vz["ff"]}
    wide = vz["d"] * vz["merge"] ** 2
    out = {n: (size[k], size[m]) for n, k, m in BLOCK_LINEARS}
    out.update(proj1=(wide, wide), proj2=(wide, vz["out"]))
    return out


def _codes(key, shape, cim, init):
    lo, hi = qrange(cim["weight_bits"])
    kz, ks = jax.random.split(key)
    k, n = shape[-2:]
    code = jnp.clip(jnp.round(jax.random.normal(kz, shape)
                              * init["code_std"]), lo, hi)
    kt = -(-k // cim["array_rows"])
    s_w = (jax.random.uniform(ks, shape[:-2] + (kt, n), jnp.float32,
                              1.0 - init["s_w_spread"],
                              1.0 + init["s_w_spread"])
           / (math.sqrt(k) * init["code_std"]))
    return {"code": code.astype(jnp.int8), "s_w": s_w}


def _make_weights(sz, key):
    vz, cim, init = vision(sz), sz["cim"], sz["init"]
    keys = iter(jax.random.split(key, 9))
    taps = 3 * vz["patch"] ** 2
    shapes = linear_shapes(sz)
    return {
        "patch_w": (jax.random.normal(next(keys), (taps, vz["d"]))
                    / math.sqrt(taps)),
        "patch_b": jax.random.normal(next(keys), (vz["d"],))
        * init["bias_std"],
        "pos": jax.random.normal(next(keys), (vz["pos"], vz["pos"], vz["d"]))
        * init["pos_std"],
        "layers": {n: _codes(next(keys), (vz["layers"],) + shapes[n], cim,
                             init) for n, _, _ in BLOCK_LINEARS},
        "proj1": _codes(next(keys), shapes["proj1"], cim, init),
        "proj2": _codes(next(keys), shapes["proj2"], cim, init),
    }


# ---------------------------------------------------------------------------
# the CIM linear
# ---------------------------------------------------------------------------

def _psums(a, code, cim):
    """Integer partial sums (n, S, k_tiles, N) of activation codes a
    (n, K): one matmul per bit split over the array-tiled rows (exact:
    integers in float32 at HIGHEST)."""
    k, n_out = code.shape
    rows = cim["array_rows"]
    kt = -(-k // rows)
    a = jnp.pad(a, ((0, 0), (0, kt * rows - k))).reshape(-1, kt, rows)
    c = code.astype(jnp.int32)
    mag, sgn = jnp.abs(c), jnp.sign(c)
    base = 2 ** cim["cell_bits"]
    out = []
    for s in range(n_split(cim)):
        d = (sgn * ((mag // base ** s) % base)).astype(jnp.float32)
        d = jnp.pad(d, ((0, kt * rows - k), (0, 0))).reshape(kt, rows, n_out)
        out.append(jnp.einsum("ntr,trc->ntc", a, d, precision=HIGHEST))
    return jnp.stack(out, axis=1)


def _act_codes(x, s_a, cim):
    lo, hi = qrange(cim["act_bits"])
    return jnp.clip(jnp.round(x / s_a), lo, hi)


def _adc_dequant(p, s_p, s_w, s_a, cim):
    lo, hi = qrange(cim["psum_bits"])
    q = jnp.clip(jnp.round(p / s_p), lo, hi) * s_p
    places = jnp.asarray([2.0 ** (cim["cell_bits"] * s)
                          for s in range(n_split(cim))], jnp.float32)
    deq = places[:, None, None] * s_w[None]
    return jnp.einsum("nstc,stc->nc", q, deq, precision=HIGHEST) * s_a


def _blocked(f, x, block=BLOCK):
    """f over row blocks of x, one block at a time."""
    b = math.gcd(x.shape[0], block)
    y = jax.lax.map(f, x.reshape((x.shape[0] // b, b) + x.shape[1:]))
    return y.reshape((x.shape[0],) + y.shape[2:])


def cim_linear(x, lw, ls, cim):
    s_a = ls["s_a"][0]
    return _blocked(lambda xb: _adc_dequant(
        _psums(_act_codes(xb, s_a, cim), lw["code"], cim), ls["s_p"],
        lw["s_w"], s_a, cim), x)


def calibrate_linear(x, lw, cim, spread):
    """The linear's output, with its scales set from x: ``s_a`` and every
    (split, tile, column) ``s_p`` at ``spread`` root-mean-squares over
    ``q_p``."""
    _, qa = qrange(cim["act_bits"])
    _, qp = qrange(cim["psum_bits"])
    s_a = spread * jnp.sqrt(jnp.mean(jnp.square(x))) / qa + 1e-9
    p = _psums(_act_codes(x, s_a, cim), lw["code"], cim)
    s_p = spread * jnp.sqrt(jnp.mean(jnp.square(p), axis=0)) / qp + 1e-9
    y = _adc_dequant(p, s_p, lw["s_w"], s_a, cim)
    return y, {"s_a": s_a.reshape(1), "s_p": s_p}


# ---------------------------------------------------------------------------
# the tower
# ---------------------------------------------------------------------------

def bicubic(n_out, n_in, a=-0.75):
    """(n_out, n_in) weights of a bicubic resize, half-pixel centres,
    clamped borders."""
    m = np.zeros((n_out, n_in))
    for i in range(n_out):
        x = (i + 0.5) * n_in / n_out - 0.5
        x0 = math.floor(x)
        t = x - x0
        for j, dist in enumerate((1 + t, t, 1 - t, 2 - t)):
            w = (((a + 2) * dist - (a + 3)) * dist * dist + 1 if dist <= 1
                 else ((a * dist - 5 * a) * dist + 8 * a) * dist - 4 * a)
            m[i, min(max(x0 + j - 1, 0), n_in - 1)] += w
    return m.astype(np.float32)


def layer_norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rope_2d(x, rows, cols, theta):
    """x (n, heads, hd): pair (2j, 2j+1) turned by column (j even) or row
    (j odd) times w_{j//2}."""
    hd = x.shape[-1]
    w = 1.0 / (theta ** (jnp.arange(0, hd, 4, dtype=jnp.float32) / hd))
    ang = jnp.stack([cols[:, None] * w, rows[:, None] * w], axis=-1)
    ang = ang.reshape(-1, 1, hd // 2)
    re, im = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([re * c - im * s, re * s + im * c],
                     axis=-1).reshape(x.shape)


def attend(qkv, rows, cols, vz):
    n = qkv.shape[0]
    qkv = qkv.reshape(n, 3, vz["heads"], vz["hd"])
    q = rope_2d(qkv[:, 0], rows, cols, vz["theta"])
    k = rope_2d(qkv[:, 1], rows, cols, vz["theta"])
    v = qkv[:, 2]

    def block(qb):
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(vz["hd"]))
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)
    return _blocked(block, q).reshape(n, vz["d"])


def _grid(sz, hw):
    """(h, w) patches of an (H, W) image, and each patch's row and column
    as float32 (h w,) arrays."""
    p = vision(sz)["patch"]
    h, w = hw[0] // p, hw[1] // p
    return (h, w), (jnp.repeat(jnp.arange(h, dtype=jnp.float32), w),
                    jnp.tile(jnp.arange(w, dtype=jnp.float32), h))


def _embed(sz, weights, img, dt):
    """One (H, W, 3) image -> its (h w, d) patch tokens: the patch
    embedding plus the resized position table."""
    vz = vision(sz)
    p, g = vz["patch"], vz["pos"]
    (h, w), _ = _grid(sz, img.shape)
    x = img.reshape(h, p, w, p, 3).transpose(0, 2, 1, 3, 4).reshape(h * w, -1)
    x = jnp.dot(_rnd(x, dt), weights["patch_w"], precision=HIGHEST) \
        + weights["patch_b"]
    pos = jnp.einsum("hH,HWd->hWd", bicubic(h, g), weights["pos"],
                     precision=HIGHEST)
    pos = jnp.einsum("wW,hWd->hwd", bicubic(w, g), pos, precision=HIGHEST)
    return _rnd(x + pos.reshape(h * w, -1), dt)


def _attn(sz, x, wl, sl, hw, dt, lin):
    """A block's attention core on one image's (h w, d) block input x:
    LayerNorm, the fused q, k, v linear, 2-D RoPE, attention; up to the
    output linear's input. ``lin`` runs a CIM linear (x, weights, scales)
    -> (y, scales found)."""
    vz = vision(sz)
    _, (rows, cols) = _grid(sz, hw)
    y, found = lin(_rnd(layer_norm(_rnd(x, dt), vz["eps"]), dt), wl["wqkv"],
                   sl["wqkv"])
    return _rnd(attend(_rnd(y, dt), rows, cols, vz), dt), found


def _attn_out(x, att, wl, sl, dt, lin):
    """The residual after a block's attention half: x + Wo att."""
    y, found = lin(_rnd(att, dt), wl["wo"], sl["wo"])
    return _rnd(_rnd(x, dt) + y, dt), found


def _mlp(sz, x, wl, sl, dt, lin):
    """The block's output from the residual x after its attention half:
    x + W2 GELU_tanh(W1 LN(x))."""
    x = _rnd(x, dt)
    y, found = lin(_rnd(layer_norm(x, vision(sz)["eps"]), dt), wl["wu"],
                   sl["wu"])
    y, found2 = lin(_rnd(jax.nn.gelu(y, approximate=True), dt), wl["wd"],
                    sl["wd"])
    return _rnd(x + y, dt), (found, found2)


def _block(sz, x, wl, sl, hw, dt, lin):
    """One pre-norm block over one image's (h w, d) tokens."""
    sl = sl or {n: None for n in wl}
    found = {}
    att, found["wqkv"] = _attn(sz, x, wl, sl, hw, dt, lin)
    x, found["wo"] = _attn_out(x, att, wl, sl, dt, lin)
    x, (found["wu"], found["wd"]) = _mlp(sz, x, wl, sl, dt, lin)
    return x, found


def _project(sz, x, weights, scales, hw, dt, lin):
    """The last block's (h w, d) output of one image -> its (h w / 4, out)
    projector tokens: the final LayerNorm, the projector's LayerNorm, the
    2 x 2 merge, Linear, GELU, Linear."""
    vz = vision(sz)
    (h, w), _ = _grid(sz, hw)
    ln = functools.partial(layer_norm, eps=vz["eps"])
    scales = scales or {"proj1": None, "proj2": None}
    x = _rnd(ln(_rnd(ln(_rnd(x, dt)), dt)), dt)
    m = vz["merge"]
    x = x.reshape(h // m, m, w // m, m, -1).transpose(0, 2, 1, 3, 4)
    x = x.reshape((h // m) * (w // m), -1)
    y, s1 = lin(x, weights["proj1"], scales["proj1"])
    y = _rnd(jax.nn.gelu(_rnd(y, dt), approximate=False), dt)
    y, s2 = lin(y, weights["proj2"], scales["proj2"])
    return _rnd(y, dt), {"proj1": s1, "proj2": s2}


def _linear(sz, calibrating):
    cim, spread = sz["cim"], sz["calibration"]["spread"]

    def lin(x, lw, ls):
        if calibrating:
            return calibrate_linear(x, lw, cim, spread)
        return cim_linear(x, lw, ls, cim), None
    return lin


def _forward(sz, weights, scales, img, control=None):
    """One (H, W, 3) image -> its (h w / 4, out) projector tokens. With
    ``scales`` None every CIM linear calibrates on this image, and the
    scales are returned beside the tokens."""
    dt, hw = DTYPES[control], img.shape[:2]
    calibrating = scales is None
    lin = _linear(sz, calibrating)
    x, found_layers = jax.lax.scan(
        lambda x, ws: _block(sz, x, ws[0], ws[1], hw, dt, lin),
        _embed(sz, weights, img, dt),
        (weights["layers"], None if calibrating else scales["layers"]))
    y, found = _project(sz, x, weights, scales, hw, dt, lin)
    if calibrating:
        return y, dict(found, layers=found_layers)
    return y


def _layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _block_stages(sz, lin, weights, scales, i, x, att, mid, hw, control):
    """Block i's three stages, each on its given input: the attention
    core on x, the attention half's residual on (x, att), the MLP half on
    mid."""
    wl, sl = _layer(weights["layers"], i), _layer(scales["layers"], i)
    dt = DTYPES[control]
    return (_attn(sz, x, wl, sl, hw, dt, lin)[0],
            _attn_out(x, att, wl, sl, dt, lin)[0],
            _mlp(sz, mid, wl, sl, dt, lin)[0])


@functools.lru_cache(maxsize=None)
def _fns(sz_json):
    sz = json.loads(sz_json)
    lin = _linear(sz, False)
    return {
        "make_weights": jax.jit(functools.partial(_make_weights, sz)),
        "calibrate": jax.jit(lambda w, x: _forward(sz, w, None, x)[1]),
        "embeddings": jax.jit(functools.partial(_forward, sz),
                              static_argnums=(3,)),
        "embed": jax.jit(lambda w, img, c: _embed(sz, w, img, DTYPES[c]),
                         static_argnums=(2,)),
        "block_stages": jax.jit(functools.partial(_block_stages, sz, lin),
                                static_argnums=(6, 7)),
        "project": jax.jit(lambda w, s, x, hw, c: _project(
            sz, x, w, s, hw, DTYPES[c], lin)[0], static_argnums=(3, 4)),
    }


def _key(sz):
    return json.dumps(sz, sort_keys=True)


def make_weights(sz, key):
    """Float patch embedding and position table; for every CIM linear its
    int8 codes and (array tile, column) scales. One jitted call."""
    return _fns(_key(sz))["make_weights"](key)


def calibrate(sz, weights, img):
    """Per CIM linear {"s_a" (1,), "s_p" (S, k_tiles, N)} (stacked over
    the layers for a block's linears) from one float32 pass over one
    image."""
    return _fns(_key(sz))["calibrate"](weights, img)


def embeddings(sz, weights, scales, img, control=None):
    """(h w / 4, out) projector tokens of one (H, W, 3) image; ``control``
    names a lower precision (``DTYPES``; None: float32 at HIGHEST)."""
    return _fns(_key(sz))["embeddings"](weights, scales, img, control)


# The tower one stage at a time, each stage on a given input, for a
# comparison that hands every stage the program's own input: ``hw`` is
# the image's (H, W) in pixels, ``control`` as for ``embeddings``.

def embed(sz, weights, img, control=None):
    """(h w, d) patch tokens of one (H, W, 3) image, before the blocks."""
    return _fns(_key(sz))["embed"](weights, img, control)


def block_stages(sz, weights, scales, layer, x, att, mid, hw,
                 control=None):
    """Block ``layer``'s three stages on one image, each on its given
    (h w, d) input: (the attention core's output from the block input
    ``x``, the residual after the attention half from ``x`` and the
    attention core's output ``att``, the block's output from the residual
    ``mid`` after the attention half)."""
    return _fns(_key(sz))["block_stages"](weights, scales, layer, x, att,
                                          mid, tuple(hw), control)


def project(sz, weights, scales, x, hw, control=None):
    """The projector tokens of one image from the last block's (h w, d)
    output ``x``."""
    return _fns(_key(sz))["project"](weights, scales, x, tuple(hw), control)
