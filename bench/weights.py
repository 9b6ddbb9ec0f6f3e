"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the serving decode step reads (a dense decoder
stack scanned over a leading layer axis); the plain reference reads the
same tree. Matrices are normal with fan-in scaling, so logits stay near
unit scale at any width; norms and biases are drawn too, so every
parameter the architecture has takes part.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, shape_key) -> Dict:
    (V, D, L, H, KV, hd, F, tied, qkv_bias, qk_norm, dtype) = shape_key
    dt = DTYPES[dtype]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, dt) * jnp.asarray(std, dt)

    def around_one(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    out_scale = 1.0 / math.sqrt(2 * L)
    attn = {"wq": normal((L, D, H * hd), D ** -0.5),
            "wk": normal((L, D, KV * hd), D ** -0.5),
            "wv": normal((L, D, KV * hd), D ** -0.5),
            "wo": normal((L, H * hd, D), (H * hd) ** -0.5 * out_scale)}
    if qkv_bias:
        attn.update(bq=normal((L, H * hd), 0.1), bk=normal((L, KV * hd), 0.1),
                    bv=normal((L, KV * hd), 0.1))
    if qk_norm:
        attn.update(q_norm=around_one((L, hd)), k_norm=around_one((L, hd)))
    params = {
        "embed": normal((V, D), D ** -0.5),
        "final_norm": around_one((D,)),
        "layers": {
            "ln1": around_one((L, D)), "ln2": around_one((L, D)),
            "attn": attn,
            "mlp": {"w_gate": normal((L, D, F), D ** -0.5),
                    "w_up": normal((L, D, F), D ** -0.5),
                    "w_down": normal((L, F, D), F ** -0.5 * out_scale)},
        },
    }
    if not tied:
        params["lm_head"] = normal((D, V), D ** -0.5)
    return params


def make_params(arch, seed: int) -> Dict:
    """The served weights of ``arch`` (an ``ArchConfig``) from ``seed``."""
    shape_key = (arch.vocab, arch.d_model, arch.n_layers, arch.n_heads,
                 arch.n_kv_heads, arch.head_dim_, arch.d_ff,
                 arch.tie_embeddings, arch.qkv_bias, arch.qk_norm,
                 arch.param_dtype)
    return _make(key_from_seed(seed), shape_key)

