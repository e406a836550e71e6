"""Bit-splitting of integer weights across multi-bit memory cells.

A b-bit signed integer weight is stored across ``n_split = ceil(b/c)``
cells of c bits each (paper Fig. 5: "weight duplication and quantization
into bit-splits"). We use **differential sign-magnitude** encoding, the
RRAM-faithful scheme (conductances are non-negative; positive/negative
weights live on a G+/G- column pair whose analog difference feeds the
ADC — the paper's variation reference [11] models exactly such cells):

    w_int = sign(w_int) * sum_s d_s * 2^(c*s),  d_s = digit_s(|w_int|)

Each physical cell stores an unsigned digit in [0, 2^c); the sign is the
pair assignment. Collapsing the pair, the effective digit seen by the MAC
is sign(w) * d_s, so small weights have small stored digits — which is
what makes multiplicative (log-normal) cell variation benign for small
weights, unlike two's-complement encodings that represent small negative
values with large complementary digit pairs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .granularity import n_splits


def split_digits(w_int: jnp.ndarray, weight_bits: int, cell_bits: int) -> jnp.ndarray:
    """Decompose integer-valued ``w_int`` (float dtype ok) into signed-
    magnitude digits, shape (n_split,) + w_int.shape, digit s having place
    value 2**(cell_bits*s). STE: the gradient w.r.t. w_int distributes
    across digits by place value (recombine(grad) == grad)."""
    if weight_bits == 1:
        # binary weights {-1, +1}: one signed cell holds the value directly
        return w_int[None]
    s_count = n_splits(weight_bits, cell_bits)
    base = 2 ** cell_bits
    w = jax.lax.stop_gradient(w_int)
    sign = jnp.sign(w)
    # round, not truncate: a code that comes back from a float division
    # as c - eps must stay c (a cast alone would make it c - 1)
    mag = jnp.round(jnp.abs(w)).astype(jnp.int32)
    digits = []
    for s in range(s_count):
        digits.append(((mag // (base ** s)) % base).astype(w_int.dtype) * sign)
    out = jnp.stack(digits, axis=0)
    # STE: least-norm distribution of the incoming gradient over digits
    places = place_values(weight_bits, cell_bits).astype(w_int.dtype)
    norm = jnp.sum(places ** 2)
    corr = (w_int - jax.lax.stop_gradient(w_int))  # zero-valued, carries grad
    out = out + corr[None, ...] * (places / norm).reshape(
        (s_count,) + (1,) * w_int.ndim)
    return out


def place_values(weight_bits: int, cell_bits: int) -> jnp.ndarray:
    s_count = n_splits(weight_bits, cell_bits)
    return jnp.asarray([2.0 ** (cell_bits * s) for s in range(s_count)],
                       jnp.float32)


def recombine(digits: jnp.ndarray, weight_bits: int, cell_bits: int) -> jnp.ndarray:
    places = place_values(weight_bits, cell_bits).astype(digits.dtype)
    return jnp.tensordot(places, digits, axes=(0, 0))
