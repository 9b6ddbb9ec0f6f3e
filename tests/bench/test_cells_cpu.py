"""Every cell's driver end to end on the CPU at reduced widths, and the
faults the check has to catch, planted underneath a run.

The harness's look for a chip is skipped (``run_cell`` is called with
the CPU's device record); everything else is the run as the chip makes
it: the program's decode step, the elastic KV cache on a Taiji system
with the swap kernels (interpreted), the window, the KV comparison and
the plain reference. The limits are the cells' own, but for
``logit_err_max``, which is set for these widths (conftest).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.cell import ROOT, arch_config, load_cell
from bench.run import run_cell
from conftest import CPU, reduced
from repro.models import model as M

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SECONDS = 1.5


def _run(cell, seed, **kw):
    res = run_cell(cell, seed, SECONDS, False, device=CPU, **kw)
    line = json.dumps({k: v for k, v in res.items() if k != "numbers"})
    assert list(json.loads(line))[-1] == "checks"
    return res


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name):
    cell = reduced(load_cell(name))
    res = _run(cell, 2**32 + 11)
    n = res["numbers"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = {m["name"]: m.get("workloads", CELLS) for m in SPEC["end_to_end"]}
    assert set(res["metrics"]) == {m for m, cells in spec.items() if name in cells}
    assert n["kv_tokens_checked"] > 0 and n["served_tokens_checked"] > 0
    assert n["compile_events_in_window"] == 0
    swapped = n["taiji_in_window"]["ms_swapped_out"]
    if cell.traffic["kv_live_over_physical"] is None:
        assert swapped == 0
    else:
        assert swapped > 0 and n["taiji_in_window"]["mp_swapped_in"] > 0


def _cell():
    return reduced(load_cell("qwen3-4b.chat-overcommit"))


def _faulty_step(arch, fault):
    """The program's decode step with one fault planted in it."""
    def step(params, tokens, cache):
        if fault == "int4_weights":
            def q(w):
                if w.ndim < 2:
                    return w
                s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True) / 7
                return (jnp.clip(jnp.round(w / s), -7, 7) * s).astype(w.dtype)
            params = jax.tree.map(q, params)
        pos = cache["kv_len"]
        logits, new = M.decode_step(params, arch, tokens, cache)
        bt = arch.kv_block_tokens
        blk = jnp.take_along_axis(cache["block_table"], (pos // bt)[:, None], axis=1)[:, 0]
        kv = jnp.moveaxis(new["kv_pool"][:, blk, pos % bt], 0, 1)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if fault == "token_altered":
            greedy = greedy.at[0].set((greedy[0] + 1) % arch.vocab)
        if fault == "state_unchanged":
            new = dict(new, kv_pool=cache["kv_pool"])
        return logits, greedy, kv, new

    return jax.jit(step)


@pytest.mark.parametrize("fault", ["int4_weights", "token_altered", "state_unchanged"])
def test_planted_step_fault_is_not_correct(fault):
    cell = _cell()
    arch = arch_config(cell.config)
    res = _run(cell, 5, arch=arch, step=_faulty_step(arch, fault))
    assert not res["correct"], (fault, res["checks"])


def test_flipped_byte_in_taiji_is_not_correct():
    def flip_one_session(loop):
        """Every K/V token appended for one session reaches Taiji with
        one bit flipped (a session may restart, so all of them)."""
        orig = loop.kv_cache.append_kv
        target = []

        def append(seq, kv):
            if not target:
                target.append(seq)
            if seq == target[0]:
                kv = kv.copy()
                kv.view(np.uint8).reshape(-1)[5] ^= 1
            orig(seq, kv)

        loop.kv_cache.append_kv = append

    res = _run(_cell(), 6, after_setup=flip_one_session)
    assert not res["correct"]
    assert res["checks"]["kv_mismatch_bytes"]["value"] >= 1
    assert res["numbers"]["kv_mismatch_sessions"] == 1
    for name in ("logit_err_max", "logit_gap_max"):
        assert res["checks"][name]["value"] <= res["checks"][name]["limit"]


CONTROLS = ["qwen3-4b.chat-overcommit", "qwen2-0.5b.sessions-overcommit"]


@pytest.mark.parametrize("name", CONTROLS)
def test_control_is_not_correct(name):
    """The plain reference one precision step below bfloat16 (int8 and
    fp8 e4m3 weights and activations), read as the step is at the
    positions of the sampled requests, comes out not correct through
    the cell's own verdict, where the program comes out correct."""
    from bench import check

    cell = reduced(load_cell(name))
    res = run_cell(cell, 8, SECONDS, False, device=CPU, controls=("int8", "fp8"))
    assert res["correct"], res["checks"]
    for quant in ("int8", "fp8"):
        v = check.verdict(check.control_numbers(res["numbers"], quant), cell.limits)
        assert not v["correct"], (quant, v["compared"])


def test_traced_run_prints_host_layers_and_the_window():
    """--trace 1 on the CPU: the window's last second is traced; with no
    TPU in the trace the device metrics stay silent, the host ones and
    the traced window are there."""
    cell = reduced(load_cell("qwen3-4b.chat-overcommit"))
    res = run_cell(cell, 9, 2.5, True, device=CPU, trace_seconds=1.0)
    assert res["correct"]
    assert set(res["metrics"]) == {"kv_append_ms_per_step", "swapin_ms_per_request",
                                   "swap_in_p90_us"}
    assert res["device"]["busy_s"] == 0 and 0.9 < res["device"]["window_s"] < 2.0
    assert res["breakdown"]["device_ops"] == []
    assert list(res)[-2:] == ["checks", "numbers"]
