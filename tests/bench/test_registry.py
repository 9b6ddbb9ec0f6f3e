"""The harness is driven by data: cells, configurations, mixes and
metric readers are files found by name, and BENCHMARK.json agrees with
them."""
import json
import shutil

import numpy as np
import pytest

from bench.cell import BENCH, ROOT, load_cell, metric_readers
from bench.traffic.generator import SessionTraffic

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_benchmark_cells_are_files(wl):
    cell = load_cell(wl["name"])
    assert cell.workload["config"] == wl["config"]
    assert cell.workload["traffic"] == wl["traffic"]
    assert cell.chips == wl["chips"] == 1
    assert cell.workload["why"] == wl["why"]
    assert cell.config["name"] == wl["config"]
    assert set(cell.limits) == {"logit_err_max", "logit_gap_max", "kv_mismatch_bytes"}
    assert cell.limits["kv_mismatch_bytes"] == 0


def test_configs_and_metrics_have_files():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    readers = metric_readers()
    for m in SPEC["per_layer"]:
        assert m["name"] in readers, m["name"]
        assert readers[m["name"]].UNIT == m["unit"]


def test_a_new_cell_is_found_by_name(tmp_path):
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "workloads", "metrics"):
        shutil.copytree(BENCH / sub, root / sub)
    wl = json.loads((BENCH / "workloads" / "qwen3-4b.chat-overcommit.json").read_text())
    mix = json.loads((BENCH / "traffic" / "chat-overcommit.json").read_text())
    mix["clients"] = 4
    (root / "traffic" / "chat-fits-4.json").write_text(json.dumps(mix))
    wl.update(traffic="chat-fits-4", why="a cell that exists only here")
    (root / "workloads" / "qwen3-4b.chat-fits-4.json").write_text(json.dumps(wl))
    (root / "metrics" / "zero_reader.py").write_text(
        'UNIT = "1"\n\n\ndef read(rec):\n    return None\n')
    cell = load_cell("qwen3-4b.chat-fits-4", root=root)
    assert cell.traffic["clients"] == 4 and cell.config["name"] == "qwen3-4b"
    assert "zero_reader" in metric_readers(root)
    with pytest.raises(FileNotFoundError):
        load_cell("qwen3-4b.no-such-mix", root=root)


@pytest.mark.parametrize("mix", sorted(p.stem for p in (BENCH / "traffic").glob("*.json")))
def test_seeds_reorder_the_same_sizes(mix):
    """Every run seed serves the same sizes, conversation ends and
    session picks, in the same order; the seed draws only token ids."""
    m = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    a = SessionTraffic(m, 2**33 + 5, 1000)
    c = SessionTraffic(m, 17, 1000)
    work = lambda t: [t.request() + (t.pick_session(np.arange(40)),)
                      for _ in range(len(t._user))]
    wa, wc = work(a), work(c)
    assert wa == wc
    assert np.array_equal(a.histories, c.histories)
    assert not np.array_equal(a.tokens(64), c.tokens(64))
    assert a.histories.min() > 0 and a.histories.max() <= m["history"]["hi"]
    assert max(u + g - 1 for u, g, _, _ in wa) <= m["context_cap_tokens"]


@pytest.mark.parametrize("mix", sorted(p.stem for p in (BENCH / "traffic").glob("*.json")))
def test_mix_follows_its_source(mix):
    """Turn sizes keep the published means up to the clip the mix
    states, and conversations last the published number of turns."""
    m = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    t = SessionTraffic(m, 1, 1000)
    assert m["source"] and m["reduced"]
    assert abs(t._ends.mean() - 1 / m["turns_per_conversation"]["mean"]) < 0.05
    for key, sizes in (("user_tokens", t._user), ("output_tokens", t._out)):
        spec = m[key]
        assert sizes.min() >= spec["lo"] and sizes.max() <= spec["hi"]
        assert 0.75 * spec["mean"] < sizes.mean() <= 1.05 * spec["mean"]
