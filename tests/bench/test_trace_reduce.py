"""The trace reduction, on a hand-made event list and on a small trace
recorded on one TPU v5e (the events ``trace_reduce.load`` kept from a
4-second traced window of ``qwen3-4b.chat-overcommit``)."""
from pathlib import Path

import pytest

from bench import counts
from bench import trace_reduce as T

RECORDED = Path(__file__).parent / "data" / "trace_qwen3_chat_overcommit.json.gz"


def test_stable_names():
    assert T.stable_name("jit_step(12)") == "jit_step"
    assert T.stable_name("fusion.12") == "fusion"
    assert T.stable_name("copy.3.1") == "copy"
    assert T.stable_name("jit_gather_blocks") == "jit_gather_blocks"
    assert T.stable_name("%gather_blocks.1 = u32[8,2304,128]{2,1,0} custom-call("
                         "s32[8]{0} %indices.1)") == "gather_blocks"


def _ev(kind, name, start, dur, chip=0):
    return (kind, chip if kind != "host" else -1, name, float(start), float(dur))


def test_busy_union_and_labelled_gaps():
    events = [
        _ev("host", T.WINDOW_SPAN, 0, 100),
        _ev("host", "bench.step_dispatch", 0, 10),
        _ev("host", "bench.readback", 10, 30),
        _ev("host", "bench.admit", 60, 30),
        _ev("module", "jit_step(3)", 10, 30),
        _ev("op", "fusion.1", 10, 20),
        _ev("op", "fusion.2", 25, 15),         # overlaps fusion.1
        _ev("op", "custom-call.7", 70, 10),
        _ev("op", "copy.1", 120, 10),          # outside the window
    ]
    r = T.reduce(events)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)           # [10,40] + [70,80]
    assert r["chips"] == 1
    assert r["op_n"] == {"fusion": 2, "custom-call": 1}
    assert r["module_s"] == {"jit_step": pytest.approx(30e-9)}
    gaps = dict(r["idle_gaps"])
    # [0,10] in dispatch; [40,70] mostly in admit (20 of 30); [80,100] admit
    assert gaps["bench.step_dispatch"] == pytest.approx(10e-9)
    assert gaps["bench.admit"] == pytest.approx(50e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.reduce([_ev("op", "fusion", 0, 1)])


@pytest.fixture(scope="module")
def recorded():
    return T.load_events(str(RECORDED))


def test_recorded_trace_names(recorded):
    r = T.reduce(recorded)
    # the decode step's jitted module and the swap kernels the reclaim
    # path called in this half second, as modules and as kernel ops
    assert r["module_n"]["jit_step"] == 16
    for k in ("gather_blocks", "zero_detect", "fletcher_checksum"):
        assert r["module_n"][f"jit_{k}"] == 2, k
        assert r["op_n"][k] == 2, k
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(0.5)
    labels = {name for name, _ in r["idle_gaps"]}
    assert labels <= {"bench.step_dispatch", "bench.readback", "bench.append_kv",
                      "bench.admit", "none"}
    assert "while" not in r["op_s"]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_recorded_swap_kernel_roofline(recorded):
    import types

    from bench.cell import metric_readers

    r = T.reduce(recorded)
    reader = metric_readers()["swap_kernel_roofline"]
    calls = [e for e in r["ops"] if T.stable_name(e[2]) in counts.SWAP_KERNELS]
    # each call moved one 9 MiB KV block: 8 MPs of 2304 rows of 512 B
    assert {reader.call_bytes(T.stable_name(e[2]), e[2]) for e in calls
            if T.stable_name(e[2]) == "gather_blocks"} == {2 * 8 * 2304 * 512}
    share = reader.read(types.SimpleNamespace(trace=r, peaks=counts.peaks("TPU v5 lite")))
    assert 0 < share < 100
