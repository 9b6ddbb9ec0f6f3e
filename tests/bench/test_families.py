"""Model families are files: the dense family keeps the weights and
counts it had before it moved into its own module, and a family of
another kind, planted with its configuration and reference in a
temporary root, runs through the harness with no file of ``bench/``
edited."""
import dataclasses
import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import counts
from bench.cell import (BENCH, ROOT, arch_config, family_module, load_cell,
                        reference_module)
from bench.run import run_cell
from bench.weights import make_params
from conftest import CPU, reduced
from repro.models import model as M

PLANTED = Path(__file__).resolve().parent / "data" / "moe_family"


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = jax.device_get(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of the reduced-width trees, taken before the dense family moved
# out of bench/weights.py
@pytest.mark.parametrize("config,seed,digest", [
    ("qwen3-4b", 2**32 + 11, "4834ff3cd58bc89533f7f3566f26852ba7fe81b0b082b75ee0089c20f0181e17"),
    ("qwen3-4b", 7, "53e63277a899c22089995ce5a7da75be987565a729480374759a32316b468375"),
    ("qwen2-0.5b", 2**32 + 11, "dfec0c780fdb318a0327851a2a813696930f5b8fbddfcb6fcee8cb7550453f61"),
])
def test_dense_weights_are_the_ones_served_before(config, seed, digest):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    arch = arch_config(family_module(cfg["family"]).reduced(cfg))
    assert _digest(make_params(arch, seed)) == digest


# full-width counts of eight rows, taken before the formulas moved into
# bench/families/dense.py
@pytest.mark.parametrize("config,nbytes,flops", [
    ("qwen3-4b", 8216615936.0, 65042907136.0),
    ("qwen2-0.5b", 1002383104.0, 8003502080.0),
])
def test_dense_decode_counts_are_the_ones_read_before(config, nbytes, flops):
    arch = arch_config(json.loads((BENCH / "configs" / f"{config}.json").read_text()))
    kv_lens = [0, 1, 63, 64, 511, 200, 17, 300]
    assert counts.decode_bytes(arch, kv_lens) == nbytes
    assert counts.decode_flops(arch, kv_lens) == flops


@pytest.fixture
def planted(tmp_path):
    """A root that holds the benchmark's traffic and metric readers, and a
    MoE family, its configuration, its reference and one cell on them."""
    root = tmp_path / "bench"
    for sub in ("traffic", "metrics"):
        shutil.copytree(BENCH / sub, root / sub)
    for sub in ("families", "reference", "configs"):
        shutil.copytree(PLANTED / sub, root / sub)
    wl = json.loads((BENCH / "workloads" / "qwen3-4b.chat-overcommit.json").read_text())
    wl.update(config="tiny-moe", why="a MoE cell that exists only here")
    (root / "workloads").mkdir()
    (root / "workloads" / "tiny-moe.chat-overcommit.json").write_text(json.dumps(wl))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": "tiny-moe.chat-overcommit", "config": "tiny-moe",
                          "traffic": "chat-overcommit", "chips": 1, "why": wl["why"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_planted_moe_family_runs_through_the_harness(planted):
    cell = load_cell("tiny-moe.chat-overcommit", root=planted)
    assert cell.root == planted
    arch = arch_config(cell.config, root=planted)
    assert arch.family == "moe" and arch.moe.first == 1 and arch.moe.n_shared == 1

    # the weights are the tree the program's MoE decoder reads, every leaf
    # counted
    params = make_params(arch, 3, root=planted)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), M.param_shapes(arch))
    assert got == want
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert counts.param_count(arch, root=planted) == n

    # one row reaches two of the four experts, eight reach all of them
    kv = [5]
    one = counts.decode_bytes(arch, kv, root=planted)
    assert one < counts.decode_bytes(arch, kv * 8, root=planted) / 1.01
    assert (counts.decode_flops(arch, kv * 2, root=planted)
            == 2 * counts.decode_flops(arch, kv, root=planted))
    assert counts.kv_token_bytes(arch, root=planted) == 3 * 2 * 2 * 16 * 2

    # the planted reference is the program's arithmetic: in float32 the
    # program's own forward pass gives its logits
    f32 = dataclasses.replace(arch, param_dtype="float32", compute_dtype="float32")
    tokens = np.random.default_rng(0).integers(0, arch.vocab, 40).astype(np.int32)
    p32 = jax.tree.map(lambda x: x.astype(np.float32), params)
    with jax.default_matmul_precision("highest"):
        hidden, _ = M.forward(p32, f32, {"tokens": tokens[None]}, remat=False)
        program = M.logits_from_hidden(p32, f32, hidden[0])
    ref = reference_module(cell.config, planted).logits_at(
        cell.config, params, tokens, np.arange(40))
    assert float(np.max(np.abs(np.asarray(program) - np.asarray(ref)))) < 1e-4

    # set-up, a window and the check, through the program's MoE decode step
    # (served in bfloat16, whose routing can differ from the float32
    # reference's where two experts' router scores nearly tie, so
    # ``correct`` is not asserted here)
    res = run_cell(reduced(cell), 2**32 + 3, 1.5, False, device=CPU)
    n = res["numbers"]
    assert res["attempted"] > 0 and list(res["checks"]) == list(cell.limits)
    assert n["kv_mismatch_bytes"] == 0 and n["kv_tokens_checked"] > 0
    assert n["served_tokens_checked"] > 0 and n["compile_events_in_window"] == 0
    assert n["taiji_in_window"]["ms_swapped_out"] > 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "tokens_per_s", "setup_s"}
