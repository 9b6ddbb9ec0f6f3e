"""An expert layer that holds a share of the experts (expert parallelism
on one device): the router scores every expert, only the held experts'
part of the result is computed, the decode dispatch drops no token and
runs the held experts through the ``expert_swiglu`` kernel, and the
serving loop takes such a share."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.reduce import reduced_config
from repro.kernels.expert_swiglu import ROW_TILE, expert_swiglu
from repro.launch import serve
from repro.models import model as M
from repro.models import moe as MO

SHARES = 8


def _cfg(held=None, n_routed=32, top_k=8):
    """The reduced Qwen3-MoE layer with ``n_routed`` experts, top-``top_k``,
    holding ``held`` (all by default)."""
    cfg = reduced_config("qwen3-moe-235b-a22b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_routed=n_routed, top_k=top_k, held=held))


def _layer(cfg, seed=0):
    return M._init_moe(jax.random.PRNGKey(seed), cfg)


def _share(p, lo, hi):
    return dict(p, **{n: p[n][lo:hi] for n in ("w_gate", "w_up", "w_down")})


def _plain(x, p, cfg):
    """Every token's top-k experts in numpy float64, gates renormalised
    over the k, experts held elsewhere left out."""
    m = cfg.moe
    lo, hi = m.held_range
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    logits = x @ w["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:m.top_k]
        gates = probs[t, top] / probs[t, top].sum()
        for e, g in zip(top, gates):
            if lo <= e < hi:
                h = x[t] @ w["w_gate"][e - lo]
                u = x[t] @ w["w_up"][e - lo]
                out[t] += g * ((h / (1 + np.exp(-h)) * u) @ w["w_down"][e - lo])
    return out


@pytest.mark.parametrize("path", ["decode", "forward"])
def test_held_shares_add_up_to_the_whole_layer(path):
    """The 8 shares' partial outputs (each device's experts, the same
    router), summed, equal the layer that holds every expert, and the
    plain layer."""
    cfg = _cfg()
    p = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model), jnp.float32)
    per = cfg.moe.n_routed // SHARES

    def run(c, params):
        if path == "decode":
            return MO.moe_decode(x, params, c)
        return MO.moe_ffn(x[None], params, c)[0][0]

    with jax.default_matmul_precision("highest"):
        whole = np.asarray(run(cfg, p))
        parts = [np.asarray(run(_cfg(held=(i * per, (i + 1) * per)),
                                _share(p, i * per, (i + 1) * per)))
                 for i in range(SHARES)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(whole, _plain(x, p, cfg), rtol=1e-4, atol=1e-5)
    share = _cfg(held=(per, 2 * per))
    np.testing.assert_allclose(parts[1], _plain(x, _share(p, per, 2 * per), share),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows", [1, 2, 5, 16, 33, 64])
@pytest.mark.parametrize("skew", [False, True])
def test_decode_dispatch_drops_no_token(rows, skew):
    """At any row count, and when every token routes to the same held
    experts (one expert then holds every row, over several row tiles),
    each token gets all of its held experts' outputs."""
    cfg = _cfg(held=(4, 12))
    p = _layer(cfg, seed=rows)
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.d_model), jnp.float32)
    if skew:                     # experts 4..11, all held, win for every token
        x = x.at[:, :8].set(jnp.abs(x[:, :8]) + 1.0)
        p = dict(p, router=p["router"].at[:8, 4:12].add(5.0))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(MO.moe_decode(x, p, cfg))
    np.testing.assert_allclose(got, _plain(x, p, cfg), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("sizes", [[3, 0, 20, 1, 9], [0, 0, 0, 0, 0], [16, 16, 0, 32, 1],
                                   [40, 0, 0, 0, 0]])
def test_expert_kernel_interpreted_equals_einsum(sizes):
    """Groups of any size, empty ones too, one spanning three row tiles,
    in a later layer of the stack: each group's rows through its
    expert's SwiGLU, as the einsum writes it."""
    rng = np.random.default_rng(len(sizes) + sum(sizes))
    L, G, D, F = 3, len(sizes), 128, 64
    m = -(-max(sum(sizes), 1) // ROW_TILE) * ROW_TILE
    x = jnp.asarray(rng.normal(size=(m, D)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s) * s[-2] ** -0.5, jnp.float32)
         for s in ((L, G, D, F), (L, G, D, F), (L, G, F, D))]
    layer = 2
    got = np.asarray(expert_swiglu(x, *w, jnp.asarray(sizes, jnp.int32),
                                   jnp.int32(layer), interpret=True))
    off = np.concatenate([[0], np.cumsum(sizes)])
    for g in range(G):
        xs = x[off[g]:off[g + 1]]
        h = jnp.einsum("td,df->tf", xs, w[0][layer, g], precision="highest")
        u = jnp.einsum("td,df->tf", xs, w[1][layer, g], precision="highest")
        want = jnp.einsum("tf,fd->td", jax.nn.silu(h) * u, w[2][layer, g],
                          precision="highest")
        np.testing.assert_allclose(got[off[g]:off[g + 1]], np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_run_serving_takes_an_expert_share():
    """The program's own serving loop serves the reduced Qwen3-MoE with a
    share of its experts: the K/V reads back exact through the swaps and
    the decode step agrees with the full forward pass on the same share."""
    cfg = serve.serving_config(reduced_config("qwen3-moe-235b-a22b"))
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=(2, 6)))
    params = serve.init_params(cfg, 0)
    assert params["layers"]["moe"]["w_gate"].shape[1] == 4
    assert params["layers"]["moe"]["router"].shape[-1] == cfg.moe.n_routed
    out = serve.run_serving(cfg, params, n_seqs=8, turns=4, batch=2,
                            prompt_len=24, gen_len=8, seed=3, verbose=False)
    assert out["kv_mismatched"] == []
    assert out["stats"]["metrics"]["ms_swapped_out"] > 0
    res = serve.check_against_reference(cfg, params, out, tol=2e-2)
    assert res["ok"], res
