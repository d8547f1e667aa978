"""The one traffic generator.  A traffic file is data; its `kind` picks the
shape of what is made:

  train_batches  `distinct` token batches of (batch, seq), uniform over the
                 vocabulary, made on the device from the seed.  Every seed
                 gives the same shapes and the same amount of work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from common import seed_key


def train_batches(seed: int, traffic: dict, vocab: int) -> list:
    """Distinct (batch, seq) int32 token batches, on the device."""
    if traffic["kind"] != "train_batches":
        raise ValueError(f"traffic kind {traffic['kind']!r}")
    k, b, s = traffic["distinct"], traffic["batch"], traffic["seq"]
    toks = jax.jit(lambda key: jax.random.randint(
        key, (k, b, s), 0, vocab, jnp.int32))(seed_key(seed, "tokens"))
    return [toks[i] for i in range(k)]
