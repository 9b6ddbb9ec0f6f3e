"""The comparison that decides ``correct``.

Three numbers, each against the limit the cell file states, over a
sample of requests the window finished (drawn from the seed, the
longest among them), against the plain float32 reference run over the
same conversation (history, earlier turns and this turn's fed tokens):

* ``logit_err_max``: over every served position and the step's
  ``TOP_K`` largest logits there, the widest distance between the
  step's logit and the reference's logit of the same token: the step's
  own numbers, so a step computed in a lower precision shows even where
  its greedy token agrees. (Their root mean square, ``logit_err_rms``,
  is printed beside it.)
* ``logit_gap_max``: the widest gap by which a served token's logit
  lies below the reference's best: a token altered after the step.
* ``kv_mismatch_bytes``: every session's K/V as Taiji holds it, read
  back after the window's swap-outs and faults, against the device pool
  the decode step wrote; exact, limit 0.

With ``controls`` the reference is also run one precision step below the
served one, and read as the step is, at the same positions:
``control_numbers`` puts its readings in the program's place, for
``verdict``; the control has to come out not correct.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .serving import TOP_K

SAMPLE_TOKENS = 512        # served tokens compared, at least
SAMPLE_REQUESTS = 8        # requests compared, at least (where finished)


def sample(requests: List[Dict], t_close: float, seed: int) -> List[Dict]:
    """Finished requests, the one with the most output tokens first,
    then others in an order drawn from ``seed`` until both minimums are
    met."""
    done = [r for r in requests if r["t_done"] is not None and r["t_done"] <= t_close]
    if not done:
        return []
    first = max(range(len(done)), key=lambda i: (done[i]["G"], -i))
    rng = np.random.default_rng([seed, 7])
    order = [first] + [i for i in rng.permutation(len(done)) if i != first]
    pick, tokens = [], 0
    for i in order:
        if tokens >= SAMPLE_TOKENS and len(pick) >= SAMPLE_REQUESTS:
            break
        pick.append(done[i])
        tokens += done[i]["G"]
    return pick


@jax.jit
def _served_gap(ref: jnp.ndarray, served: jnp.ndarray) -> jnp.ndarray:
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]


@jax.jit
def _top_err(ref: jnp.ndarray, top_v: jnp.ndarray, top_i: jnp.ndarray) -> jnp.ndarray:
    return jnp.abs(top_v - jnp.take_along_axis(ref, top_i, -1))


class _Err:
    """Widest and root-mean-square logit distance, accumulated."""

    def __init__(self) -> None:
        self.max = self.ss = 0.0
        self.n = 0

    def add(self, err: np.ndarray) -> None:
        self.max = max(self.max, float(err.max()))
        self.ss += float(np.sum(np.square(err, dtype=np.float64)))
        self.n += err.size

    def numbers(self, gap_max: float) -> Dict[str, float]:
        return {"logit_err_rms": (self.ss / self.n) ** 0.5 if self.n else 0.0,
                "logit_err_max": self.max, "logit_gap_max": gap_max}


@jax.jit
def _control(ref: jnp.ndarray, low: jnp.ndarray):
    top_v, top_i = jax.lax.top_k(low, TOP_K)
    return _served_gap(ref, top_i[:, 0]), _top_err(ref, top_v, top_i)


def logit_gaps(ref_mod, config: Dict, params, conv_tokens: List[List[int]],
               picked: List[Dict], cap: int, max_out: int,
               controls: Sequence[str] = ()) -> Dict:
    """``logit_err_rms``, ``logit_err_max`` and ``logit_gap_max`` over
    ``picked``, and under ``control`` the same of each control (one per
    precision in ``controls``). Sequences are padded to ``cap`` tokens and positions to
    ``max_out``, so every request runs one compiled reference."""
    gap_max, err, n = 0.0, _Err(), 0
    control = {q: (_Err(), [0.0]) for q in controls}
    for req in picked:
        end = req["p0"] + req["U"] + req["G"] - 1
        seq = np.zeros(cap, np.int32)
        seq[:end] = conv_tokens[req["conv"]][:end]
        G = req["G"]
        pos = np.full(max_out, req["p0"] + req["U"] - 1 + G - 1, np.int32)
        pos[:G] = req["p0"] + req["U"] - 1 + np.arange(G)
        served = np.zeros(max_out, np.int32)
        served[:G] = req["gen"]
        top_v = np.zeros((max_out, TOP_K), np.float32)
        top_i = np.zeros((max_out, TOP_K), np.int32)
        top_v[:G] = [v for v, _ in req["top"]]
        top_i[:G] = [i for _, i in req["top"]]
        ref = ref_mod.logits_at(config, params, seq, pos)
        gap = np.asarray(_served_gap(ref, jnp.asarray(served)))[:G]
        e = np.asarray(_top_err(ref, jnp.asarray(top_v), jnp.asarray(top_i)))[:G]
        gap_max = max(gap_max, float(gap.max()))
        err.add(e)
        n += G
        for q in controls:
            low = ref_mod.logits_at(config, params, seq, pos, quant=q)
            cg, ce = (np.asarray(x)[:G] for x in _control(ref, low))
            c_err, c_gap = control[q]
            c_err.add(ce)
            c_gap[0] = max(c_gap[0], float(cg.max()))
            del low
        del ref
    return dict(err.numbers(gap_max),
                control={q: e.numbers(g[0]) for q, (e, g) in control.items()},
                served_tokens_checked=n, requests_checked=len(picked))


def control_numbers(numbers: Dict, quant: str) -> Dict:
    """``numbers`` with the control ``quant``'s readings in the
    program's place."""
    return dict(numbers, **numbers["control"][quant])


def verdict(numbers: Dict, limits: Dict[str, float]) -> Dict:
    """Each compared number beside its limit, and whether all hold."""
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = (all(c["value"] <= c["limit"] for c in compared.values())
          and numbers.get("served_tokens_checked", 0) > 0
          and numbers.get("kv_tokens_checked", 0) > 0)
    return {"correct": bool(ok), "compared": compared}

