"""The general traffic generator: every input of a cell comes from
``--seed`` and the parameters in ``traffic/<mix>.json``.

A mix names its unit of work (``unit``) and the end-to-end rate it
reports (``rate``); the other keys are the unit's parameters. Inputs are
drawn per distinct batch from the seed, ``distinct_batches`` of them, and
the window cycles through them: the same seed gives the same inputs, and
every seed gives the same sizes.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words from a seed of any size and a stream number.
    (``jax.random.PRNGKey`` keeps only the low 32 bits of a Python int
    when 64-bit types are off; this keeps all of them.)"""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), int(seed) >> 64,
                                 int(stream)])
    return ss.generate_state(2, dtype=np.uint32)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, stream))


def jax_key(seed: int, stream: int):
    """A raw threefry key (uint32[2]) for the seed and stream."""
    import jax.numpy as jnp
    return jnp.asarray(seed_words(seed, stream), jnp.uint32)


# streams: one per kind of input, so adding one never moves another
# (calibration inputs are batch -1 of the image stream)
WEIGHTS, IMAGES, SAMPLE = 1, 4, 5


def pixels(seed: int, batch_index: int, batch: int, hw: int,
           channels: int = 3) -> np.ndarray:
    """(batch, hw, hw, channels) uint8 pixels, uniform."""
    g = rng(seed, IMAGES * 1_000_003 + batch_index)
    return g.integers(0, 256, size=(batch, hw, hw, channels), dtype=np.uint8)


def normalize_pixels(p):
    """uint8 pixels -> float32 inputs (p - 128) / 64, exact in bfloat16."""
    return (p.astype(np.float32) - 128.0) / 64.0


def sample(seed: int, population: int, k: int) -> np.ndarray:
    """``k`` distinct indices below ``population`` drawn from the seed,
    sorted; all of them when ``k >= population``."""
    g = rng(seed, SAMPLE)
    k = min(k, population)
    return np.sort(g.choice(population, size=k, replace=False))
