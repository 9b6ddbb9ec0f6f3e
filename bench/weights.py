"""Seeded random weights, made on the device in one jitted call by the
configuration's family module (``bench/families/<family>.py``)."""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def make_params(arch, seed: int, root: Optional[Path] = None) -> Dict:
    """The served weights of ``arch`` (an ``ArchConfig``) from ``seed``."""
    from bench.cell import family_module

    return family_module(arch.family, root).make_params(arch, seed)
