"""Shared transformer building blocks: norms, RoPE, GQA/MLA attention
(KV-chunked flash-style for long contexts), SwiGLU/GELU MLPs, and the
expert-parallel MoE block. All stored-weight matmuls route through
``apply_linear`` so the paper's CIM quantization applies uniformly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn.linear import apply_linear, linear_specs
from repro.nn.module import ParamSpec, constrain, shard_map

NEG_INF = -1e30


def cdt(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32


def pdt(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# conv (CIM-aware entry point, mirrors nn.linear.apply_linear)
# ---------------------------------------------------------------------------

def conv_specs(
    kh: int, kw: int, c_in: int, c_out: int,
    *,
    cim: Optional["CIMConfig"] = None,
    out_axis: Optional[str] = None,
    dtype=jnp.float32,
) -> Dict[str, ParamSpec]:
    """ParamSpecs for a CIM conv layer (HWIO weight + paper scale factors).

    In deploy mode the weight exists ONLY as the packed 6-D digit planes
    the fused Pallas conv kernel consumes (see repro.api.pack_conv); emulate
    keeps the float HWIO weight for QAT. The out_axis lands on the planes'
    last (C_out) axis — the column-shard axis of mesh-aware deploy serving
    (DESIGN.md §10), matching ``DeployArtifact.shard``'s placement."""
    from repro.api.backends import (conv_plane_tiling, has_own_pack,
                                    is_packed, plane_bits)
    from repro.core.granularity import conv_tiling

    packed = is_packed(cim)
    if packed:
        # plane geometry is the backend's (binary: S=1 sign planes)
        t, cpa = conv_plane_tiling(cim, kh, kw, c_in, c_out)
        own_pack = has_own_pack(cim)
        if own_pack:
            cpa_s, store = cpa, cim.store_dtype()
        else:
            # standard v4 pack: int4 planes store nibble-packed along the
            # channel-slice axis and carry a w_occ map (DESIGN.md §14)
            from repro.core.nibble import stored_rows
            cpa_s, store = stored_rows(cpa, cim.store_dtype())
        specs = {"w_digits": ParamSpec(
            (t.n_split, t.k_tiles, kh, kw, cpa_s, c_out), store,
            "zeros", (None, None, None, None, None, out_axis))}
        if not own_pack:
            specs["w_occ"] = ParamSpec(
                (t.n_split, t.k_tiles, c_out), jnp.uint8, "zeros",
                (None, None, out_axis))
    else:
        # He init over the full receptive field (kh*kw*c_in), matching
        # init_cim_conv — ParamSpec's "fan_in" string would only see c_in
        fan = kh * kw * c_in
        he = lambda k, s, d: (jax.random.normal(k, s, jnp.float32)
                              * jnp.sqrt(2.0 / fan)).astype(d)
        specs = {"w": ParamSpec((kh, kw, c_in, c_out), dtype, he,
                                (None, None, None, out_axis))}
    if cim is not None and cim.enabled:
        if packed and plane_bits(cim) != (cim.weight_bits, cim.cell_bits):
            # plane-geometry backends (binary) store FULL column-
            # granularity scales (see nn.linear.linear_specs)
            from repro.core.granularity import Granularity
            t, _ = conv_plane_tiling(cim, kh, kw, c_in, c_out)
            wg = t.weight_scale_shape(Granularity.COLUMN)
            pg = t.psum_scale_shape(Granularity.COLUMN)
        else:
            t, _ = conv_tiling(kh, kw, c_in, c_out, cim.array_rows,
                               cim.array_cols, cim.weight_bits, cim.cell_bits)
            wg = t.weight_scale_shape(cim.weight_granularity)
            pg = t.psum_scale_shape(cim.psum_granularity)
        specs["s_w"] = ParamSpec(wg, jnp.float32, "const:0.05",
                                 (None, out_axis if wg[1] == c_out else None))
        specs["s_p"] = ParamSpec(pg, jnp.float32, "const:8.0",
                                 (None, None,
                                  out_axis if pg[2] == c_out else None))
        specs["s_a"] = ParamSpec((1,), jnp.float32, "ones", (None,))
    return specs


def apply_conv(
    params: Dict,
    x: jnp.ndarray,
    cim: Optional["CIMConfig"] = None,
    *,
    stride: int = 1,
    padding: str = "SAME",
    compute_dtype=jnp.bfloat16,
    variation_key: Optional[jax.Array] = None,
    variation_std=None,
) -> jnp.ndarray:
    """Conv dispatch: plain XLA conv without CIM, else the CIM framework
    (emulate grouped conv / fused Pallas deploy kernel). The variation
    knobs evaluate one Monte-Carlo cell-noise realization; emulate and
    deploy agree bit-exactly under a shared key (DESIGN.md §8)."""
    if cim is None or not cim.enabled:
        return jax.lax.conv_general_dilated(
            x.astype(compute_dtype), params["w"].astype(compute_dtype),
            (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    from repro.api import conv2d
    return conv2d(x, params, cim, stride=stride, padding=padding,
                  variation_key=variation_key,
                  variation_std=variation_std,
                  compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_specs(cfg: ModelConfig, dim: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = dim or cfg.d_model
    if cfg.norm == "nonparam_ln":          # olmo: no learnable affine
        return {}
    return {"scale": ParamSpec((d,), jnp.float32, "ones", ("embed",))}


def apply_norm(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm" or cfg.norm == "nonparam_ln":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    else:                                   # rmsnorm
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    if "scale" in p:
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def head_norm_specs(cfg: ModelConfig, hd: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((hd,), jnp.float32, "ones", (None,))}


def apply_head_rmsnorm(p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_2d(x: jnp.ndarray, rows: jnp.ndarray, cols: jnp.ndarray,
            theta: float) -> jnp.ndarray:
    """2-D rotary embedding of image patches (MoonViT). x: (..., T, H, hd);
    rows, cols: (T,) patch positions. The head's hd/2 coordinate pairs
    are adjacent elements (2j, 2j+1); pair 2i turns by ``cols * w_i``
    and pair 2i+1 by ``rows * w_i``, w_i = theta^(-4i/hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 4, dtype=jnp.float32) / hd))
    ang = jnp.stack([cols.astype(jnp.float32)[:, None] * freqs,
                     rows.astype(jnp.float32)[:, None] * freqs], axis=-1)
    ang = ang.reshape(ang.shape[0], hd // 2)[:, None, :]      # (T, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (hd // 2, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention core (full + KV-chunked flash-style)
# ---------------------------------------------------------------------------

def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, t, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, h, n_rep, d)
                            ).reshape(b, t, h * n_rep, d)


def attention(
    q: jnp.ndarray,              # (B, Tq, H, hd)
    k: jnp.ndarray,              # (B, Tk, KvH, hd)
    v: jnp.ndarray,              # (B, Tk, KvH, hdv)
    *,
    causal: bool,
    q_offset: int | jnp.ndarray = 0,
    kv_len: Optional[jnp.ndarray] = None,   # valid KV length (decode)
    chunk: int = 0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Softmax attention; online-softmax scan over KV chunks when
    ``chunk`` is set and Tk > chunk (bounded memory for 32k prefill)."""
    b, tq, h, hd = q.shape
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    sc = scale if scale is not None else (1.0 / jnp.sqrt(hd).astype(jnp.float32))
    tk = k.shape[1]

    if not chunk or tk <= chunk:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * sc
        mask = _build_mask(tq, tk, causal, q_offset, kv_len)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    # --- chunked online softmax -------------------------------------------
    n_chunks = (tk + chunk - 1) // chunk
    pad = n_chunks * chunk - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, v.shape[-1]).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        m, l, acc, c_idx = carry
        kb, vb = inp                                   # (B, C, H, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * sc
        kpos = c_idx * chunk + jnp.arange(chunk)
        valid = kpos < tk
        if kv_len is not None:
            valid = valid[None, :] & (kpos[None, :] < kv_len[:, None])
            valid = valid[:, None, None, :]
        else:
            valid = valid[None, None, None, :]
        if causal:
            qpos = _qpos(q_offset, tq)                        # (B|1, tq)
            cmask = (qpos[:, :, None] >= kpos[None, None, :])[:, None]
            valid = valid & cmask
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc_new, c_idx + 1), None

    m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    a0 = jnp.zeros((b, h, tq, v.shape[-1]), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, 0), (kc, vc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)       # (B, Tq, H, hdv)


def _qpos(q_offset, tq):
    """(B, tq) or (1, tq) query positions from scalar or (B,) offset."""
    off = jnp.asarray(q_offset)
    if off.ndim == 0:
        off = off[None]
    return off[:, None] + jnp.arange(tq)[None, :]


def _build_mask(tq, tk, causal, q_offset, kv_len):
    parts = []
    kpos = jnp.arange(tk)
    if causal:
        qpos = _qpos(q_offset, tq)                            # (B|1, tq)
        parts.append((qpos[:, :, None] >= kpos[None, None, :])[:, None])
    if kv_len is not None:
        parts.append((kpos[None, :] < kv_len[:, None])[:, None, None, :])
    if not parts:
        return None
    mask = parts[0]
    for p in parts[1:]:
        mask = mask & p
    return mask


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_specs(cfg: ModelConfig) -> Dict:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = pdt(cfg)
    sp = {
        "wq": linear_specs(d, h * hd, cim=cfg.cim, in_axis="embed",
                           out_axis="heads", dtype=dt),
        "wk": linear_specs(d, kvh * hd, cim=cfg.cim, in_axis="embed",
                           out_axis="heads", dtype=dt),
        "wv": linear_specs(d, kvh * hd, cim=cfg.cim, in_axis="embed",
                           out_axis="heads", dtype=dt),
        "wo": linear_specs(h * hd, d, cim=cfg.cim, in_axis="heads",
                           out_axis="embed", dtype=dt),
    }
    if cfg.qk_norm:
        sp["q_norm"] = head_norm_specs(cfg, hd)
        sp["k_norm"] = head_norm_specs(cfg, hd)
    return sp


def gqa_attend(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, *,
    positions: jnp.ndarray,
    cache: Optional[Dict] = None,        # {"k","v","len"} decode cache
    causal: bool = True,
    x_kv: Optional[jnp.ndarray] = None,  # cross-attention source
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    b, t, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    src = x if x_kv is None else x_kv
    q = apply_linear(p["wq"], x, cfg.cim, compute_dtype=cdt(cfg)
                     ).reshape(b, t, h, hd)
    k = apply_linear(p["wk"], src, cfg.cim, compute_dtype=cdt(cfg)
                     ).reshape(b, src.shape[1], kvh, hd)
    v = apply_linear(p["wv"], src, cfg.cim, compute_dtype=cdt(cfg)
                     ).reshape(b, src.shape[1], kvh, hd)
    if cfg.qk_norm:
        q = apply_head_rmsnorm(p["q_norm"], q)
        k = apply_head_rmsnorm(p["k_norm"], k)
    if x_kv is None and cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions if cache is None else positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and x_kv is None:
        idx = cache["len"]                                   # (B,) int32
        kv8 = "k_scale" in cache                             # int8 KV cache
        ep = _flash_decode_ep_ready(cfg, t, cache["k"].shape[1],
                                    cache["k"].shape[0])
        if kv8:
            kq, ks = _kv_quantize(k)
            vq, vs = _kv_quantize(v)
        if ep is not None and not kv8:
            # sequence-parallel flash decode: cache stays time-sharded on
            # 'model'; each shard attends over its slice, partials merge
            # with one tiny psum (no per-layer cache all-gathers)
            out, kc, vc = _flash_decode_ep(q, k, v, cache["k"], cache["v"],
                                           idx, cfg, ep)
            new_cache = {"k": kc, "v": vc, "len": idx + t}
        elif ep is not None and kv8:
            out, kc, vc, ksc, vsc = _flash_decode_ep(
                q, kq, vq, cache["k"], cache["v"], idx, cfg, ep,
                k_scale_new=ks, v_scale_new=vs,
                k_scale=cache["k_scale"], v_scale=cache["v_scale"])
            new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc,
                         "len": idx + t}
        else:
            # write new K/V at position len, attend over the prefix
            def dus3(c, n, i):
                return jax.lax.dynamic_update_slice(c, n, (i, 0, 0))
            if kv8:
                kc = jax.vmap(dus3)(cache["k"], kq, idx)
                vc = jax.vmap(dus3)(cache["v"], vq, idx)
                ksc = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
                    c, n, (i, 0)))(cache["k_scale"], ks, idx)
                vsc = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
                    c, n, (i, 0)))(cache["v_scale"], vs, idx)
                new_cache = {"k": kc, "v": vc, "k_scale": ksc,
                             "v_scale": vsc, "len": idx + t}
                k_at = (kc.astype(jnp.float32)
                        * ksc[..., None]).astype(k.dtype)
                v_at = (vc.astype(jnp.float32)
                        * vsc[..., None]).astype(v.dtype)
            else:
                kc = jax.vmap(dus3)(cache["k"], k, idx)
                vc = jax.vmap(dus3)(cache["v"], v, idx)
                new_cache = {"k": kc, "v": vc, "len": idx + t}
                k_at, v_at = kc, vc
            out = attention(q, k_at, v_at, causal=True, q_offset=idx,
                            kv_len=idx + t, chunk=cfg.attn_chunk)
    else:
        out = attention(q, k, v, causal=causal and x_kv is None,
                        chunk=cfg.attn_chunk)
    y = apply_linear(p["wo"], out.reshape(b, t, h * hd), cfg.cim,
                     compute_dtype=cdt(cfg))
    return y, new_cache


def _kv_quantize(x: jnp.ndarray):
    """Per-(token, head) symmetric int8 quantization of K/V rows — the
    paper's column-wise-scale idea applied to the decode cache (each
    head-row gets its own scale, so heterogeneous heads survive 8 bits).
    x: (B, T, KvH, hd) -> (int8 codes, (B, T, KvH) scales)."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


# --- sequence-parallel flash decode (shard_map over the KV time shards) ----

def _flash_decode_ep_ready(cfg: ModelConfig, t: int, t_cache: int,
                           b: int = 0):
    """Returns the mesh when the EP flash-decode path applies: single new
    token, a production mesh in scope, cache time/batch dims divisible."""
    from repro.nn.module import current_mesh
    mesh = current_mesh()
    if (mesh is None or t != 1 or "model" not in mesh.axis_names
            or not cfg.flash_decode
            or t_cache % mesh.shape["model"] != 0):
        return None
    nb = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            nb *= mesh.shape[a]
    if b and b % nb != 0:
        return None
    return mesh


def _flash_decode_ep(q, k_new, v_new, kc, vc, idx, cfg: ModelConfig, mesh,
                     k_scale_new=None, v_scale_new=None,
                     k_scale=None, v_scale=None):
    """q: (B,1,H,hd); k_new/v_new: (B,1,KvH,hd) (int8 codes when scales are
    given); kc/vc: (B,T,KvH,hd) time-sharded over 'model'; idx: (B,).
    Returns (out, kc, vc[, k_scale, v_scale])."""
    from jax.sharding import PartitionSpec as P
    batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b, _, h, hd = q.shape
    kvh = k_new.shape[2]
    n_rep = h // kvh
    t_total = kc.shape[1]
    t_loc = t_total // mesh.shape["model"]
    sc = 1.0 / jnp.sqrt(float(hd))
    kv8 = k_scale is not None

    def local(qb, kn, vn, kcb, vcb, ib, ksn, vsn, ksb, vsb):
        my = jax.lax.axis_index("model")
        t0 = my * t_loc
        li = ib - t0                                          # (B,)
        write = (li >= 0) & (li < t_loc)
        safe = jnp.clip(li, 0, t_loc - 1)

        def upd(c, n, i, w):
            updated = jax.lax.dynamic_update_slice(c, n, (i, 0, 0))
            return jnp.where(w, updated, c)
        kcb = jax.vmap(upd)(kcb, kn, safe, write)
        vcb = jax.vmap(upd)(vcb, vn, safe, write)
        if kv8:
            def upd2(c, n, i, w):
                updated = jax.lax.dynamic_update_slice(c, n, (i, 0))
                return jnp.where(w, updated, c)
            ksb = jax.vmap(upd2)(ksb, ksn, safe, write)
            vsb = jax.vmap(upd2)(vsb, vsn, safe, write)
            k_at = (kcb.astype(jnp.float32) * ksb[..., None]).astype(qb.dtype)
            v_at = (vcb.astype(jnp.float32) * vsb[..., None]).astype(qb.dtype)
        else:
            k_at, v_at = kcb, vcb

        kk = _repeat_kv(k_at, n_rep)                          # (B,Tl,H,hd)
        vv = _repeat_kv(v_at, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kk,
                       preferred_element_type=jnp.float32) * sc
        kpos = t0 + jnp.arange(t_loc)
        valid = kpos[None, :] < (ib + 1)[:, None]             # (B,Tl)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                           # (B,H,1)
        m_g = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_g[..., None])
        l_loc = jnp.sum(p, axis=-1)
        acc_loc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(jnp.float32),
                             vv.astype(jnp.float32))
        l_g = jax.lax.psum(l_loc, "model")
        acc_g = jax.lax.psum(acc_loc, "model")
        out = (acc_g / jnp.maximum(l_g[..., None], 1e-30))    # (B,H,1,hd)
        out = out.transpose(0, 2, 1, 3).astype(qb.dtype)
        return out, kcb, vcb, ksb, vsb

    if kv8:
        out, kc2, vc2, ks2, vs2 = shard_map(
            local, mesh=mesh,
            in_specs=(P(batch), P(batch), P(batch),
                      P(batch, "model"), P(batch, "model"), P(batch),
                      P(batch), P(batch), P(batch, "model"),
                      P(batch, "model")),
            out_specs=(P(batch), P(batch, "model"), P(batch, "model"),
                       P(batch, "model"), P(batch, "model")),
            check_vma=False,
        )(q, k_new, v_new, kc, vc, idx, k_scale_new, v_scale_new,
          k_scale, v_scale)
        return out, kc2, vc2, ks2, vs2

    def local_bf16(qb, kn, vn, kcb, vcb, ib):
        o, kcb2, vcb2, _, _ = local(qb, kn, vn, kcb, vcb, ib,
                                    None, None, None, None)
        return o, kcb2, vcb2

    out, kc2, vc2 = shard_map(
        local_bf16, mesh=mesh,
        in_specs=(P(batch), P(batch), P(batch),
                  P(batch, "model"), P(batch, "model"), P(batch)),
        out_specs=(P(batch), P(batch, "model"), P(batch, "model")),
        check_vma=False,
    )(q, k_new, v_new, kc, vc, idx)
    return out, kc2, vc2


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V3): low-rank Q/KV compression, small decode cache
# ---------------------------------------------------------------------------

def mla_specs(cfg: ModelConfig) -> Dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = pdt(cfg)
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wq_a": linear_specs(d, m.q_lora_rank, cim=cfg.cim, in_axis="embed",
                             out_axis=None, dtype=dt),
        "q_a_norm": {"scale": ParamSpec((m.q_lora_rank,), jnp.float32, "ones", (None,))},
        "wq_b": linear_specs(m.q_lora_rank, h * qk_dim, cim=cfg.cim,
                             in_axis=None, out_axis="heads", dtype=dt),
        "wkv_a": linear_specs(d, m.kv_lora_rank + m.qk_rope_dim, cim=cfg.cim,
                              in_axis="embed", out_axis=None, dtype=dt),
        "kv_a_norm": {"scale": ParamSpec((m.kv_lora_rank,), jnp.float32, "ones", (None,))},
        "wkv_b": linear_specs(m.kv_lora_rank, h * (m.qk_nope_dim + m.v_head_dim),
                              cim=cfg.cim, in_axis=None, out_axis="heads", dtype=dt),
        "wo": linear_specs(h * m.v_head_dim, d, cim=cfg.cim, in_axis="heads",
                           out_axis="embed", dtype=dt),
    }


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def mla_attend(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, *,
    positions: jnp.ndarray,
    cache: Optional[Dict] = None,   # {"ckv","krope","len"}
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim

    q = apply_linear(p["wq_b"],
                     _rms(apply_linear(p["wq_a"], x, cfg.cim, compute_dtype=cdt(cfg)),
                          p["q_a_norm"]["scale"]),
                     cfg.cim, compute_dtype=cdt(cfg)).reshape(b, t, h, qk_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    kv_a = apply_linear(p["wkv_a"], x, cfg.cim, compute_dtype=cdt(cfg))
    ckv, k_rope = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    ckv = _rms(ckv, p["kv_a_norm"]["scale"])
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,T,1,r)

    new_cache = None
    if cache is not None:
        idx = cache["len"]
        ckv_c = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
            c, n, (i, 0)))(cache["ckv"], ckv, idx)
        kr_c = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
            c, n, (i, 0, 0)))(cache["krope"], k_rope, idx)
        new_cache = {"ckv": ckv_c, "krope": kr_c, "len": idx + t}
        ckv_full, k_rope_full, kv_len = ckv_c, kr_c, idx + t
    else:
        ckv_full, k_rope_full, kv_len = ckv, k_rope, None

    kv = apply_linear(p["wkv_b"], ckv_full, cfg.cim, compute_dtype=cdt(cfg)
                      ).reshape(b, ckv_full.shape[1], h,
                                m.qk_nope_dim + m.v_head_dim)
    k_nope, v = kv[..., :m.qk_nope_dim], kv[..., m.qk_nope_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_full,
                                  k_nope.shape[:3] + (m.qk_rope_dim,))], axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attention(qq, k, v, causal=True,
                    q_offset=(cache["len"] if cache is not None else 0),
                    kv_len=kv_len, chunk=cfg.attn_chunk,
                    scale=1.0 / jnp.sqrt(float(qk_dim)))
    y = apply_linear(p["wo"], out.reshape(b, t, h * m.v_head_dim), cfg.cim,
                     compute_dtype=cdt(cfg))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = pdt(cfg)
    if cfg.act == "swiglu":
        return {
            "wg": linear_specs(d, f, cim=cfg.cim, in_axis="embed", out_axis="mlp", dtype=dt),
            "wu": linear_specs(d, f, cim=cfg.cim, in_axis="embed", out_axis="mlp", dtype=dt),
            "wd": linear_specs(f, d, cim=cfg.cim, in_axis="mlp", out_axis="embed", dtype=dt),
        }
    return {
        "wu": linear_specs(d, f, cim=cfg.cim, in_axis="embed", out_axis="mlp", dtype=dt),
        "wd": linear_specs(f, d, cim=cfg.cim, in_axis="mlp", out_axis="embed", dtype=dt),
    }


def apply_mlp(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.act == "swiglu":
        g = apply_linear(p["wg"], x, cfg.cim, compute_dtype=cdt(cfg))
        u = apply_linear(p["wu"], x, cfg.cim, compute_dtype=cdt(cfg))
        return apply_linear(p["wd"], jax.nn.silu(g) * u, cfg.cim,
                            compute_dtype=cdt(cfg))
    u = apply_linear(p["wu"], x, cfg.cim, compute_dtype=cdt(cfg))
    return apply_linear(p["wd"], jax.nn.gelu(u), cfg.cim, compute_dtype=cdt(cfg))


# ---------------------------------------------------------------------------
# Mixture-of-Experts with capacity-bounded sort-free dispatch
# ---------------------------------------------------------------------------
# Experts are sharded over the "experts"->model mesh axis. Dispatch packs
# each expert's tokens into a fixed-capacity buffer via scatter (dropped on
# overflow), runs all experts as one batched einsum, and scatter-adds the
# results back weighted by the router gates. HLO FLOPs are
# capacity_factor * active-expert FLOPs — not the dense n_experts/top_k
# blow-up — which keeps the roofline's useful-compute ratio honest.

def moe_specs(cfg: ModelConfig) -> Dict:
    mo = cfg.moe
    d, f, e = cfg.d_model, mo.d_ff, mo.n_experts
    dt = pdt(cfg)
    sp = {
        "router": linear_specs(d, e, in_axis="embed", out_axis=None,
                               dtype=jnp.float32),
        "wg": ParamSpec((e, d, f), dt, "fan_in:1.0", ("experts", "embed", "mlp")),
        "wu": ParamSpec((e, d, f), dt, "fan_in:1.0", ("experts", "embed", "mlp")),
        "wd": ParamSpec((e, f, d), dt, "fan_in:1.0", ("experts", "mlp", "embed")),
    }
    if cfg.cim.enabled:
        t = cfg.cim.tiling(d, f)
        t2 = cfg.cim.tiling(f, d)
        for nm, tt, oax in (("wg", t, "mlp"), ("wu", t, "mlp"), ("wd", t2, "embed")):
            wg_s = tt.weight_scale_shape(cfg.cim.weight_granularity)
            pg_s = tt.psum_scale_shape(cfg.cim.psum_granularity)
            sp[f"{nm}_s_w"] = ParamSpec((e,) + wg_s, jnp.float32, "const:0.05",
                                        ("experts", None, oax if wg_s[1] == tt.n else None))
            sp[f"{nm}_s_p"] = ParamSpec((e,) + pg_s, jnp.float32, "const:8.0",
                                        ("experts", None, None, oax if pg_s[2] == tt.n else None))
            sp[f"{nm}_s_a"] = ParamSpec((e, 1), jnp.float32, "ones", ("experts", None))
    if mo.n_shared:
        sp["shared"] = mlp_specs(cfg, d_ff=mo.d_ff * mo.n_shared)
    return sp


#: largest packed expert bank (bytes) eligible for single-launch batched
#: dispatch — banks beyond this stream per expert via lax.map instead.
_EXPERT_BANK_BATCH_BYTES = 4 * 1024 * 1024


def _batched_experts_ok(p: Dict, nm: str, cfg: ModelConfig) -> bool:
    """Gate for the single-launch batched expert path: the plain deploy
    fast path only — kernel dispatch, unsharded mesh, saturation
    collector unarmed, unstacked (E-leading) bank that fits the VMEM
    streaming budget. Everything else keeps the proven lax.map."""
    from repro.kernels import ops as kops
    from repro.nn.module import current_mesh
    from repro.obs import adc as obs_adc
    d = p[f"{nm}_digits"]
    return (cfg.cim.mode == "deploy" and cfg.cim.use_kernel
            and getattr(d, "ndim", 0) == 5
            and not obs_adc.enabled()
            and kops.col_shards(current_mesh()) == 1
            and d.size * max(1, d.dtype.itemsize) <= _EXPERT_BANK_BATCH_BYTES)


def _batched_expert_matmul(p: Dict, nm: str, x: jnp.ndarray,
                           cfg: ModelConfig) -> jnp.ndarray:
    """All experts' capacity buffers through ONE kernel launch
    (kernels.ops.cim_matmul_experts; expert = leading grid dim) — the
    per-expert deploy prep (act codes, input tiling, fused dequant) is
    vmapped, mirroring core.cim_linear._forward_deploy per expert, and
    the kernel is bit-exact with lax.map of the per-expert kernel."""
    from repro.core.bitsplit import place_values
    from repro.core.cim_linear import (_full_psum_scale, _full_weight_scale,
                                       _tile_inputs, deploy_act_codes)
    from repro.kernels import ops as kops
    cim = cfg.cim
    digits = p[f"{nm}_digits"]
    t = cim.tiling(x.shape[-1], digits.shape[-1])
    places = place_values(cim.weight_bits, cim.cell_bits)

    def prep(xe, s_w, s_p, s_a):
        a_t = _tile_inputs(deploy_act_codes(xe, s_a, cim), t)
        pe = {"s_w": s_w, "s_p": s_p, "s_a": s_a}
        sp = _full_psum_scale(pe, t)
        deq = (places[:, None, None] * _full_weight_scale(pe, t)[None]
               * jnp.maximum(s_a, 1e-9))
        return a_t, sp, deq

    a_t, sp, deq = jax.vmap(prep)(x, p[f"{nm}_s_w"], p[f"{nm}_s_p"],
                                  p[f"{nm}_s_a"])
    y = kops.cim_matmul_experts(a_t, digits, sp, deq,
                                psum_bits=cim.psum_bits,
                                psum_quant=cim.psum_quant)
    return y.astype(cdt(cfg))


def _expert_matmul(p: Dict, nm: str, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: (E, C, K) -> (E, C, N), optionally CIM-quantized per expert."""
    if not cfg.cim.enabled:
        return jnp.einsum("eck,ekn->ecn", x, p[nm].astype(cdt(cfg)),
                          preferred_element_type=cdt(cfg))
    from repro.api import linear
    from repro.api.backends import is_packed
    if is_packed(cfg.cim) and f"{nm}_digits" in p:
        # packed expert bank (pack_model): per-expert digit planes with
        # per-expert column scales, dispatched through the fused deploy
        # path. Small deploy banks take the single-launch batched kernel;
        # otherwise lax.map (scan) rather than vmap: pallas_call carries
        # no batching rule, and the column-sharded kernel wrapper is
        # already proven under scan by the stacked-layer serving path.
        if _batched_experts_ok(p, nm, cfg):
            return _batched_expert_matmul(p, nm, x, cfg)
        has_occ = f"{nm}_occ" in p   # v4 banks: per-expert occupancy maps
        def one(args):
            xe, d, s_w, s_p, s_a = args[:5]
            node = {"w_digits": d, "s_w": s_w, "s_p": s_p, "s_a": s_a}
            if has_occ:
                node["w_occ"] = args[5]
            return linear(xe, node, cfg.cim, compute_dtype=cdt(cfg))
        operands = (x, p[f"{nm}_digits"], p[f"{nm}_s_w"],
                    p[f"{nm}_s_p"], p[f"{nm}_s_a"])
        if has_occ:
            operands += (p[f"{nm}_occ"],)
        return jax.lax.map(one, operands)
    # unpacked tree on a packed backend: fall back to emulate (identical
    # quantization arithmetic; only the storage layout differs)
    ecfg = (cfg.cim if not is_packed(cfg.cim)
            else cfg.cim.replace(mode="emulate"))
    def one(xe, we, s_w, s_p, s_a):
        return linear(xe, {"w": we, "s_w": s_w, "s_p": s_p, "s_a": s_a},
                      ecfg, compute_dtype=cdt(cfg))
    return jax.vmap(one)(x, p[nm].astype(jnp.float32), p[f"{nm}_s_w"],
                         p[f"{nm}_s_p"], p[f"{nm}_s_a"])


def apply_moe(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Dispatches to the shard_map expert-parallel path when lowering on a
    production mesh (experts sharded over 'model'); pure-jit fallback
    elsewhere (single device, tests)."""
    from repro.nn.module import current_mesh
    mesh = current_mesh()
    # packed expert banks (nm_digits planes) serve through the jit path:
    # their parallelism is column sharding inside the kernel wrapper
    # (DESIGN.md §10), not expert-parallel shard_map over raw banks
    packed_banks = any(k.endswith("_digits") for k in p)
    if (cfg.moe_impl != "jit" and not packed_banks and mesh is not None
            and "model" in mesh.axis_names
            and cfg.moe.n_experts % mesh.shape["model"] == 0):
        return _apply_moe_ep(p, x, cfg, mesh)
    return _apply_moe_jit(p, x, cfg)


def _apply_moe_jit(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    mo = cfg.moe
    b, t, d = x.shape
    n_tok = b * t
    e, k = mo.n_experts, mo.top_k
    xf = x.reshape(n_tok, d)

    logits = apply_linear(p["router"], xf.astype(jnp.float32), None,
                          compute_dtype=jnp.float32)          # (N, E)
    gates, sel = jax.lax.top_k(logits, k)                     # (N, k)
    gates = jax.nn.softmax(gates, axis=-1) if mo.router_scale else jax.nn.sigmoid(gates)

    # per-expert buffer slots. Every expert processes its full buffer, so
    # total expert FLOPs = e * cap * ffn — dropless (cap = n_tok*k) is only
    # affordable for tiny test workloads; production uses the capacity
    # factor (decode at B=128/E=256: 1.33x active FLOPs, not 64x).
    cap = int(mo.capacity_factor * n_tok * k / e) + 1
    if n_tok * k <= 256:
        cap = n_tok * k
    flat_e = sel.reshape(-1)                                  # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(n_tok), k)
    flat_g = gates.reshape(-1)

    # position of each (token, expert) pair within its expert's buffer
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    start = jnp.searchsorted(e_sorted, jnp.arange(e), side="left")
    pos_in_e = jnp.arange(n_tok * k) - start[e_sorted]
    slot_sorted = jnp.where(pos_in_e < cap, e_sorted * cap + pos_in_e,
                            e * cap)                          # overflow -> dropped
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    slot = slot_sorted[inv]                                   # (N*k,)

    buf = jnp.zeros((e * cap + 1, d), cdt(cfg)).at[slot].set(
        xf.astype(cdt(cfg))[flat_tok], mode="drop")
    buf = buf[:-1].reshape(e, cap, d)
    buf = constrain(buf, ("experts", None, None))   # EP: experts on 'model'

    if cfg.act == "swiglu":
        g = _expert_matmul(p, "wg", buf, cfg)
        u = _expert_matmul(p, "wu", buf, cfg)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(cdt(cfg)) * u
    else:
        h = jax.nn.gelu(_expert_matmul(p, "wu", buf, cfg).astype(jnp.float32)
                        ).astype(cdt(cfg))
    out_buf = _expert_matmul(p, "wd", h, cfg).reshape(e * cap, d)
    out_buf = constrain(out_buf, ("experts", None))
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, d), out_buf.dtype)], 0)

    y = jnp.zeros((n_tok, d), jnp.float32).at[flat_tok].add(
        out_buf[slot].astype(jnp.float32) * flat_g[:, None], mode="drop")
    y = constrain(y.astype(cdt(cfg)), ("batch", None))
    if mo.n_shared:
        y = y + apply_mlp(p["shared"], xf, cfg)
    return y.reshape(b, t, d)


# --- shard_map expert parallelism -------------------------------------------
# Key observation: at the MoE block the activations are replicated across
# the 'model' mesh axis (TP blocks psum before it) and sharded over the
# batch axes. Sharding experts over 'model' therefore needs NO all_to_all:
# every model-shard already holds the tokens, routes deterministically,
# gathers only the tokens its local experts own (capacity-bounded), runs
# its expert FFNs, scatter-adds its partial output, and ONE psum over
# 'model' merges expert partials — bytes per layer = activations, not the
# e*cap dispatch buffer the auto-SPMD path was replicating.

def _apply_moe_ep(p: Dict, x: jnp.ndarray, cfg: ModelConfig, mesh):
    from jax.sharding import PartitionSpec as P
    mo = cfg.moe
    b, t, d = x.shape
    batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ep = mesh.shape["model"]
    e_local = mo.n_experts // ep

    def local_moe(xf, router_w, wg, wu, wd, extra):
        # xf: (n_tok_local, d) — identical across the 'model' axis
        n_loc = xf.shape[0]
        k = mo.top_k
        logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))
        gates, sel = jax.lax.top_k(logits, k)                 # (n_loc, k)
        gates = (jax.nn.softmax(gates, axis=-1) if mo.router_scale
                 else jax.nn.sigmoid(gates))
        my = jax.lax.axis_index("model")
        lo = my * e_local
        cap = max(int(mo.capacity_factor * n_loc * k / mo.n_experts) + 1, 4)
        if n_loc * k <= 256:
            cap = n_loc * k

        flat_e = sel.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(n_loc), k)
        flat_g = gates.reshape(-1)
        mine = (flat_e >= lo) & (flat_e < lo + e_local)
        le = jnp.where(mine, flat_e - lo, e_local)            # local expert id
        order = jnp.argsort(le, stable=True)
        le_sorted = le[order]
        start = jnp.searchsorted(le_sorted, jnp.arange(e_local), side="left")
        pos = jnp.arange(n_loc * k) - start[jnp.clip(le_sorted, 0, e_local - 1)]
        slot_sorted = jnp.where(
            (le_sorted < e_local) & (pos < cap),
            le_sorted * cap + pos, e_local * cap)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
        slot = slot_sorted[inv]

        buf = jnp.zeros((e_local * cap + 1, d), cdt(cfg)).at[slot].set(
            xf.astype(cdt(cfg))[flat_tok], mode="drop")
        buf = buf[:-1].reshape(e_local, cap, d)

        def mm(w, z, nm):
            if not cfg.cim.enabled:
                return jnp.einsum("eck,ekn->ecn", z, w.astype(cdt(cfg)),
                                  preferred_element_type=cdt(cfg))
            from repro.api import linear
            from repro.api.backends import is_packed
            ecfg = (cfg.cim if not is_packed(cfg.cim)
                    else cfg.cim.replace(mode="emulate"))
            s_w, s_p, s_a = (extra[f"{nm}_s_w"], extra[f"{nm}_s_p"],
                             extra[f"{nm}_s_a"])
            return jax.vmap(lambda ze, we, a_, b_, c_: linear(
                ze, {"w": we, "s_w": a_, "s_p": b_, "s_a": c_}, ecfg,
                compute_dtype=cdt(cfg)))(z, w.astype(jnp.float32), s_w,
                                         s_p, s_a)

        if cfg.act == "swiglu":
            h = jax.nn.silu(mm(wg, buf, "wg").astype(jnp.float32)
                            ).astype(cdt(cfg)) * mm(wu, buf, "wu")
        else:
            h = jax.nn.gelu(mm(wu, buf, "wu").astype(jnp.float32)
                            ).astype(cdt(cfg))
        out_buf = mm(wd, h, "wd").reshape(e_local * cap, d)
        out_buf = jnp.concatenate(
            [out_buf, jnp.zeros((1, d), out_buf.dtype)], 0)
        y = jnp.zeros((n_loc, d), jnp.float32).at[flat_tok].add(
            out_buf[slot].astype(jnp.float32) * flat_g[:, None], mode="drop")
        return jax.lax.psum(y.astype(jnp.float32), "model").astype(cdt(cfg))

    extra = {kk: p[kk] for kk in p
             if kk.startswith(("wg_", "wu_", "wd_"))} if cfg.cim.enabled else {}
    espec = {kk: P("model") for kk in extra}
    xf = x.reshape(b * t, d)
    y = shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(batch, None), P(), P("model"), P("model"), P("model"),
                  espec),
        out_specs=P(batch, None),
        check_vma=False,
    )(xf, p["router"]["w"], p["wg"], p["wu"], p["wd"], extra)
    if mo.n_shared:
        y = y + apply_mlp(p["shared"], xf, cfg)
    return y.reshape(b, t, d)
