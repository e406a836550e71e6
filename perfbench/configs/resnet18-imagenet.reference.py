"""Plain reference for ResNet-18 under the paper's CIM quantization.

Straightforward ``jax.numpy`` in float32 at HIGHEST precision, written
from the published description and imports nothing of the program:

- ResNet-18 (He et al. 2016, arXiv:1512.03385): four stages of two basic
  blocks (3x3 conv, BN, ReLU, 3x3 conv, BN, plus the shortcut, ReLU) at
  widths 64/128/256/512; the first block of stages 2-4 strides by 2 and
  projects its shortcut with a strided 1x1 conv and BN; global average
  pool and a 1000-way fully connected layer. The stem here is a float
  3x3 stride-1 conv with BN and ReLU and no max-pool (see the config's
  ``reduced``); stem and classifier stay full precision, as is usual in
  CIM quantization and in the paper.
- The paper's CIM conv (arXiv:2502.07842, section III-C): each array of
  ``array_rows`` rows holds ``floor(array_rows / (kh*kw))`` whole input
  channels with all their taps; every (bit split, array tile, output
  channel) partial sum is quantized by a ``psum_bits`` ADC with its own
  scale, dequantized by ``2^(cell_bits*split) * s_w * s_a`` and added up.
  Activations are ``act_bits`` signed codes with one scale per conv;
  weights ``weight_bits`` signed codes with one scale per (array tile,
  output channel), split sign-magnitude over ``cell_bits`` cells.

The weights are made here from the seed; ``calibrate`` sets every conv's
``s_a``/``s_p`` to the power of two nearest to a few standard deviations
of what reaches them over seeded calibration images. Every BN is folded
into the conv scales, as a CIM deployment folds it: it carries the
identity statistics (mean 0, var 1 - eps, so that var + eps is exactly
1). With the stem's weights on an 8-bit fixed-point grid, the images on
one too, and every CIM scale a power of two, every value up to the
average pool is exact in float32 whatever the order of its sums: an
implementation that computes the same semantics in float32 agrees with
this one there bit for bit, and differs only by the rounding of the
classifier's dot. The control computes the same in bfloat16, the step
below the float32 that the configuration states: it rounds every
activation through bfloat16.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
DN = ("NHWC", "HWIO", "NHWC")
DTYPES = {None: None, "bfloat16": jnp.bfloat16}


def _rnd(x, dt):
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def qrange(bits):
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def n_split(cim):
    return math.ceil(cim["weight_bits"] / cim["cell_bits"])


def tiling(kh, c_in, cim):
    """(channels per array, array tiles) of a kh x kh conv."""
    cpa = max(1, cim["array_rows"] // (kh * kh))
    return cpa, math.ceil(c_in / cpa)


def blocks(sz):
    """(name, c_in, c_out, stride, projected) of every basic block."""
    out, c_in = [], sz["widths"][0]
    for si, w in enumerate(sz["widths"]):
        for bi in range(sz["blocks_per_stage"]):
            stride = 2 if (bi == 0 and si > 0) else 1
            out.append((f"s{si}b{bi}", c_in, w, stride,
                        stride != 1 or c_in != w))
            c_in = w
    return out


def convs(sz):
    """(block, conv, kh, c_in, c_out, stride) of every CIM conv."""
    out = []
    for name, c_in, w, stride, proj in blocks(sz):
        out.append((name, "conv1", 3, c_in, w, stride))
        out.append((name, "conv2", 3, w, w, 1))
        if proj:
            out.append((name, "proj", 1, c_in, w, stride))
    return out


def _bf16_values(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def pow2(s):
    """The power of two nearest to each positive float32 ``s`` (in log2),
    built from its exponent bits so that it is exact."""
    k = jnp.round(jnp.log2(s)).astype(jnp.int32)
    return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)


def _make_weights(sz, key):
    cim, init = sz["cim"], sz["init"]
    lo, hi = qrange(cim["weight_bits"])
    cs = convs(sz)
    keys = iter(jax.random.split(key, 2 + 2 * len(cs)))
    w0 = sz["widths"][0]
    grid = 2.0 ** init["stem_frac_bits"]
    stem = jax.random.normal(next(keys), (3, 3, 3, w0)) * math.sqrt(2.0 / 27)
    out = {"stem": jnp.clip(jnp.round(stem * grid), -255, 255) / grid,
        "fc": _bf16_values(jax.random.normal(
            next(keys), (sz["widths"][-1], sz["n_classes"]))
            / math.sqrt(sz["widths"][-1]))}
    for blk, conv, kh, c_in, c_out, _ in cs:
        z = jax.random.normal(next(keys), (kh, kh, c_in, c_out))
        code = jnp.clip(jnp.round(z * init["code_std"]), lo, hi)
        base = math.sqrt(2.0 / (kh * kh * c_in)) / init["code_std"]
        _, kt = tiling(kh, c_in, cim)
        s_w = base * jax.random.uniform(
            next(keys), (kt, c_out), jnp.float32,
            1.0 - init["s_w_spread"], 1.0 + init["s_w_spread"])
        # powers of two: code * s_w over s_w stays exact under any float32
        # division (the program's pack truncates, see PERF.md)
        out[f"{blk}.{conv}"] = {"code": code.astype(jnp.int8),
                                "s_w": pow2(s_w)}
    return out


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _psums(a, code, stride, cim):
    """Integer partial sums (B, H', W', S, kt, C_out) of activation codes
    a (B, H, W, C_in): one grouped conv per bit split, one group per
    array tile (exact: integer values in float32 at HIGHEST)."""
    kh, _, c_in, c_out = code.shape
    cpa, kt = tiling(kh, c_in, cim)
    pad = kt * cpa - c_in
    a = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pad)))
    c = code.astype(jnp.int32)
    mag, sgn = jnp.abs(c), jnp.sign(c)
    base = 2 ** cim["cell_bits"]
    out = []
    for s in range(n_split(cim)):
        d = (sgn * ((mag // base ** s) % base)).astype(jnp.float32)
        d = jnp.pad(d, ((0, 0), (0, 0), (0, pad), (0, 0)))
        d = d.reshape(kh, kh, kt, cpa, c_out).transpose(0, 1, 3, 2, 4)
        p = jax.lax.conv_general_dilated(
            a, d.reshape(kh, kh, cpa, kt * c_out), (stride, stride),
            "SAME", dimension_numbers=DN, feature_group_count=kt,
            precision=HIGHEST)
        out.append(p.reshape(p.shape[:3] + (kt, c_out)))
    return jnp.stack(out, axis=3)


def _act_codes(x, s_a, cim):
    lo, hi = qrange(cim["act_bits"])
    return jnp.clip(jnp.round(x / s_a), lo, hi)


def _adc(p, s_p, cim):
    lo, hi = qrange(cim["psum_bits"])
    return jnp.clip(jnp.round(p / s_p), lo, hi) * s_p


def _dequant(q, s_w, s_a, cim):
    places = jnp.asarray([2.0 ** (cim["cell_bits"] * s)
                          for s in range(n_split(cim))], jnp.float32)
    deq = places[:, None, None] * s_w[None]
    return jnp.einsum("bhwstc,stc->bhwc", q, deq, precision=HIGHEST) * s_a


def cim_conv(x, lw, ls, stride, cim):
    s_a = ls["s_a"][0]
    p = _psums(_act_codes(x, s_a, cim), lw["code"], stride, cim)
    return _dequant(_adc(p, ls["s_p"], cim), lw["s_w"], s_a, cim)


def calibrate_conv(x, lw, stride, cim, spread):
    _, qa = qrange(cim["act_bits"])
    _, qp = qrange(cim["psum_bits"])
    s_a = pow2(spread * jnp.sqrt(jnp.mean(jnp.square(x))) / qa + 1e-9)
    p = _psums(_act_codes(x, s_a, cim), lw["code"], stride, cim)
    s_p = pow2(spread * jnp.sqrt(jnp.mean(jnp.square(p), axis=(0, 1, 2)))
               / qp + 1e-9)
    y = _dequant(_adc(p, s_p, cim), lw["s_w"], s_a, cim)
    return y, {"s_a": s_a.reshape(1), "s_p": s_p}


def _bn(x, st):
    return (x - st["mean"]) * jax.lax.rsqrt(st["var"] + 1e-5)


def _bn_folded(x):
    """Identity statistics: (x - 0) * rsqrt((1 - eps) + eps) is x."""
    c = x.shape[-1]
    return {"mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.full((c,), 1.0 - 1e-5, jnp.float32)}


def _forward(sz, weights, scales, bn, x, control, calibrating=False):
    """x (B, H, W, 3) -> logits (B, n_classes). With ``calibrating`` the
    scales and BN statistics are set from this batch and returned."""
    cim, spread = sz["cim"], sz["calibration"]["spread"]
    dt = DTYPES[control]
    found_s, found_bn = {}, {}

    def norm(name, y):
        if calibrating:
            found_bn[name] = _bn_folded(y)
        return _rnd(_bn(y, (found_bn if calibrating else bn)[name]), dt)

    def conv(name, h, stride):
        if calibrating:
            y, found_s[name] = calibrate_conv(h, weights[name], stride, cim,
                                              spread)
        else:
            y = cim_conv(h, weights[name], scales[name], stride, cim)
        return _rnd(y, dt)

    h = _rnd(jax.lax.conv_general_dilated(
        _rnd(x, dt), weights["stem"], (1, 1), "SAME", dimension_numbers=DN,
        precision=HIGHEST), dt)
    h = jax.nn.relu(norm("stem_bn", h))
    for name, _, _, stride, proj in blocks(sz):
        y = jax.nn.relu(norm(f"{name}.bn1", conv(f"{name}.conv1", h, stride)))
        y = norm(f"{name}.bn2", conv(f"{name}.conv2", y, 1))
        sc = (norm(f"{name}.bn_p", conv(f"{name}.proj", h, stride))
              if proj else h)
        h = _rnd(jax.nn.relu(y + sc), dt)
    h = _rnd(jnp.mean(h, axis=(1, 2)), dt)
    logits = _rnd(jnp.dot(h, weights["fc"], precision=HIGHEST), dt)
    return (logits, found_s, found_bn) if calibrating else logits


@functools.lru_cache(maxsize=None)
def _fns(sz_json):
    sz = json.loads(sz_json)
    return {
        "make_weights": jax.jit(functools.partial(_make_weights, sz)),
        "calibrate": jax.jit(lambda w, x: _forward(sz, w, None, None, x,
                                                   None, True)[1:]),
        "logits": jax.jit(functools.partial(_forward, sz),
                          static_argnums=(4,)),
    }


def _key(sz):
    return json.dumps(sz, sort_keys=True)


def make_weights(sz, key):
    """Integer conv codes (HWIO, int8) and (tile, channel) scales for
    every CIM conv, the stem weights (8-bit fixed point) and classifier
    weights (bfloat16 values), in float32. One jitted call."""
    return _fns(_key(sz))["make_weights"](key)


def calibrate(sz, weights, x):
    """(scales, bn): per conv {"s_a", "s_p" (S, kt, C_out)}, powers of two,
    from one float32 pass over x; per BN its folded {"mean", "var"}."""
    return _fns(_key(sz))["calibrate"](weights, x)


def logits(sz, weights, scales, bn, x, control=None):
    """Logits (B, n_classes) of images x; ``control`` names a lower
    precision (``DTYPES``; None: float32 at HIGHEST)."""
    return _fns(_key(sz))["logits"](weights, scales, bn, x, control)
