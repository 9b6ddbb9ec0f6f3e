"""The expert-parallel Qwen3-MoE configuration in the harness: its
family's counts at full width, its plain reference against the
program's arithmetic, the controls and planted faults its check has to
catch at reduced widths, and the readers of its expert kernel."""
import copy
import dataclasses
import json
import types

import jax
import numpy as np
import pytest

from bench import check, counts
from bench.cell import BENCH, arch_config, family_module, load_cell, metric_readers, reference_module
from bench.run import run_cell
from bench.weights import make_params
from conftest import CPU, reduced
from repro.models import model as M
from test_cells_cpu import SECONDS, _faulty_step, _run

CELL = "qwen3-235b-a22b-ep8.chat-overcommit-64"
CONFIG = json.loads((BENCH / "configs" / "qwen3-235b-a22b-ep8.json").read_text())

# At these widths, over seeds 11-20 on the CPU, the program's
# logit_err_max reached 0.116 where the int8 control's fell to 0.10
# (tokens routed past a near tie in the router, PERF.md section 2), so
# no limit on the widest error tells them apart at these widths (at
# full width the cell's own limit does); their root mean squares do:
# the program 0.011-0.015, int8 0.026-0.033, fp8 0.097-0.121.
REDUCED_LIMITS = {"logit_err_max": 0.2, "logit_err_rms": 0.02}


def _reduced():
    """The cell at reduced widths, held to limits set for those widths."""
    cell = reduced(load_cell(CELL))
    wl = copy.deepcopy(cell.workload)
    wl["limits"].update(REDUCED_LIMITS)
    return dataclasses.replace(cell, workload=wl)


def test_counts_at_full_width():
    """Qwen3-235B-A22B's published widths, this chip's share: 373.8 M
    parameters a layer (attention 71.3 M, router 0.5 M, 16 experts
    302 M), 4.64 B in all, 24 KiB of K/V a token; the served tree has as
    many. A step of 64 rows reaches 98.4% of the held experts."""
    arch = arch_config(CONFIG)
    assert (arch.moe.n_routed, arch.moe.n_held, arch.moe.top_k) == (128, 16, 8)
    shapes = jax.eval_shape(lambda: make_params(arch, 0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert counts.param_count(arch) == n == 4_641_496_064
    layer = (n - 2 * 18992 * 4096 - 4096) // 12
    assert layer == 71_303_168 + 2 * 128 + 2 * 4096 + 524_288 + 16 * 3 * 4096 * 1536
    assert counts.kv_token_bytes(arch) == 12 * 2 * 4 * 128 * 2 == 24576
    fam = family_module("moe")
    reach = 1 - (1 - 8 / 128) ** 64
    assert fam.reach(arch, 64) == pytest.approx(reach) and reach > 0.98
    expert = 3 * 4096 * 1536
    kv_lens = [100] * 64
    weights = n - 18992 * 4096 + 64 * 4096 - 12 * 16 * expert * (1 - reach)
    assert counts.decode_bytes(arch, kv_lens) == pytest.approx(
        2 * weights + 24576 * (100 * 64 + 64))
    # a token computes one held expert a layer on average (8 x 16 / 128)
    per_token = 12 * (71_303_168 + 524_288 + expert) + 4096 * 18992
    assert counts.decode_flops(arch, [0]) == 2 * per_token + 4 * 12 * 64 * 128


def test_reference_is_the_programs_arithmetic():
    """In float32 the program's own forward pass over the share gives
    the plain reference's logits: the same routing over 128 experts, the
    same 16 experts, the same gates."""
    cell = reduced(load_cell(CELL))
    arch = arch_config(cell.config)
    params = make_params(arch, 3)
    f32 = dataclasses.replace(arch, param_dtype="float32", compute_dtype="float32")
    tokens = np.random.default_rng(0).integers(0, arch.vocab, 40).astype(np.int32)
    p32 = jax.tree.map(lambda x: x.astype(np.float32), params)
    with jax.default_matmul_precision("highest"):
        hidden, _ = M.forward(p32, f32, {"tokens": tokens[None]}, remat=False)
        program = M.logits_from_hidden(p32, f32, hidden[0])
    ref = reference_module(cell.config).logits_at(cell.config, params, tokens, np.arange(40))
    assert float(np.max(np.abs(np.asarray(program) - np.asarray(ref)))) < 1e-4
    # a share that holds other experts computes something else
    moved = dict(cell.config, deployment=dict(cell.config["deployment"], held_experts_first=16))
    other = reference_module(cell.config).logits_at(moved, params, tokens, np.arange(40))
    assert float(np.max(np.abs(np.asarray(other) - np.asarray(ref)))) > 1e-2


@pytest.mark.parametrize("seed", [11, 14, 18])
def test_controls_are_not_correct(seed):
    """The program comes out correct and both controls not, at seeds
    where the program's widest error passes the int8 control's."""
    cell = _reduced()
    res = run_cell(cell, seed, SECONDS, False, device=CPU, controls=("int8", "fp8"))
    assert res["correct"], res["checks"]
    for quant in ("int8", "fp8"):
        v = check.verdict(check.control_numbers(res["numbers"], quant), cell.limits)
        assert not v["correct"], (quant, v["compared"])


@pytest.mark.parametrize("fault", ["int4_weights", "token_altered", "state_unchanged"])
def test_planted_step_fault_is_not_correct(fault):
    cell = _reduced()
    arch = arch_config(cell.config)
    res = _run(cell, 13, arch=arch, step=_faulty_step(arch, fault))
    assert not res["correct"], (fault, res["checks"])


def test_flipped_byte_in_taiji_is_not_correct():
    def flip(loop):
        """Every K/V token appended for the first session the window
        serves reaches Taiji with one bit flipped."""
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

    res = _run(_reduced(), 16, after_setup=flip)
    assert not res["correct"]
    assert res["checks"]["kv_mismatch_bytes"]["value"] >= 1
    for name in ("logit_err_max", "logit_gap_max"):
        assert res["checks"][name]["value"] <= res["checks"][name]["limit"]


def _rec(ops, steps=10):
    """A traced run's record with ``ops`` (name, device ns) in its trace."""
    arch = arch_config(CONFIG)
    trace = {"ops": [("op", 0, name, 0.0, ns) for name, ns in ops],
             "op_s": {}, "module_n": {"jit_step": steps}, "module_s": {}}
    for _, _, name, _, ns in trace["ops"]:
        key = name[1:].split(" ", 1)[0].split(".")[0]
        trace["op_s"][key] = trace["op_s"].get(key, 0.0) + ns / 1e9
    loop = types.SimpleNamespace(steps=[{"t0": 1.0 + i, "t_ready": 1.5 + i,
                                          "kv_lens": np.full(64, 100)}
                                         for i in range(steps)])
    return types.SimpleNamespace(arch=arch, cell=load_cell(CELL), trace=trace,
                                 peaks=counts.peaks("TPU v5 lite"), loop=loop,
                                 trace_span=(0.0, 100.0))


def test_expert_readers():
    """Each call of the kernel in a step of 64 rows needs the 16 held
    experts' weights as far as 64 rows reach them; the readers say
    nothing where the kernel never ran."""
    readers = metric_readers()
    call = "%expert_swiglu.13 = bf16[752,4096]{1,0:T(8,128)(2,1)} custom-call(%a, %b)"
    rec = _rec([(call, 1_000_000)] * 120 + [("%fusion.1 = bf16[8]{0} fusion(%x)", 5e5)])
    assert readers["moe_expert_ms_per_step"].read(rec) == pytest.approx(12.0)
    arch = arch_config(CONFIG)
    need = family_module("moe").expert_call_bytes(arch, 64)
    assert need == pytest.approx(2 * (16 * (1 - (1 - 1 / 16) ** 64) * 3 * 4096 * 1536
                                      + 2 * 64 * 1 * 4096))
    want = 100 * 120 * need / 8.19e11 / 0.12
    assert readers["moe_expert_roofline"].read(rec) == pytest.approx(want)
    silent = _rec([("%fusion.1 = bf16[8]{0} fusion(%x)", 5e5)])
    assert readers["moe_expert_ms_per_step"].read(silent) is None
    assert readers["moe_expert_roofline"].read(silent) is None
