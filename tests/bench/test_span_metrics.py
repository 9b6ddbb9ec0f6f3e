"""The program-span readers: the tracer's clock mapped onto the
profiler's, on synthetic anchors and on a CPU profiler run, and every
reader on a hand-built run record."""
import json
import time
import types

import numpy as np
import pytest

from bench import trace_reduce
from bench.cell import metric_readers
from bench.metrics import _spans
from repro.core.elastic_kv import ElasticKVCache, KVGeometry, make_kv_taiji_config
from repro.core.system import TaijiSystem
from repro.obs import map_clock
from repro.obs.tracer import ST_KV_APPEND, ST_SCHED_TASK, SpanTracer

NEW = ("pin_ms_per_request", "pin_reclaim_ms_per_request",
       "pin_decode_ms_per_request", "kv_alloc_ms_per_step",
       "background_busy_frac", "idle_background_frac")
CELL = types.SimpleNamespace(name="qwen3-4b.chat-overcommit")


def test_map_clock_on_synthetic_anchors():
    # the other clock runs at half the rate, 50 ns ahead at perf 1000
    anchors = ((1000, 50.0), (3000, 1050.0))
    got = map_clock(np.array([1000, 2000, 3000, 5000]), *anchors)
    assert got.tolist() == [50.0, 550.0, 1050.0, 2050.0]
    assert map_clock(1000 + 400, *anchors) - map_clock(1000, *anchors) == 200.0
    assert float(map_clock(0, (0, 7.0), (10, 17.0))) == 7.0


@pytest.fixture
def out_dirs(tmp_path, monkeypatch):
    monkeypatch.setattr(_spans, "TRACE_ROOT", tmp_path / "trace")
    monkeypatch.setattr(_spans, "SPANS_ROOT", tmp_path / "spans")
    return tmp_path


def test_cpu_profiler_kv_append_spans_fall_in_their_annotations(out_dirs):
    """Appends to a traced elastic KV cache inside ``bench.append_kv``
    annotations, a millisecond of host work around each, under a CPU
    profiler: every mapped ``kv_append`` span lands inside its own
    annotation, within 20 us."""
    import jax
    from jax.profiler import TraceAnnotation

    geom = KVGeometry(n_layers=2, kv_heads=2, head_dim=16, block_tokens=4)
    system = TaijiSystem(make_kv_taiji_config(geom, 6))
    cache = ElasticKVCache(geom, system)
    cache.create_sequence(0)
    kv = np.ones((2, 2, 2, 16), np.float16)
    trace_dir = out_dirs / "trace" / CELL.name

    def pad():
        t = time.perf_counter()
        while time.perf_counter() - t < 1e-3:
            pass

    try:
        jax.profiler.start_trace(str(trace_dir))
        with TraceAnnotation(trace_reduce.WINDOW_SPAN):
            t_open = time.perf_counter()
            for _ in range(10):
                with TraceAnnotation(_spans.APPEND_SPAN):
                    pad()
                    cache.append_kv(0, kv)
                    pad()
                pad()
            t_close = time.perf_counter()
        jax.profiler.stop_trace()
    finally:
        system.close()
    events = trace_reduce.load(trace_reduce.find_xplane(str(trace_dir)))
    lo, hi = trace_reduce.window(events)
    device_op = ("op", 0, "fusion", lo, (hi - lo) / 2)
    rec = types.SimpleNamespace(
        cell=CELL, trace_span=(t_open, t_close),
        trace={"chips": 1, "ops": [device_op], "modules": []},
        loop=types.SimpleNamespace(system=system, stats_open={}, stats_close={}))
    sp = _spans.traced(rec)
    assert sp["clock_check"]["spans"] == 10
    assert sp["clock_check"]["unmatched"] == 0
    assert sp["clock_check"]["max_overhang_us"] <= 20.0
    assert sp["retained"]["window_complete"]
    assert _spans.traced(rec) is sp                # cached for every reader
    doc = json.loads((out_dirs / "spans" / f"{CELL.name}.json").read_text())
    assert doc["clock_check"] == sp["clock_check"]


def _stages(**totals):
    return {name: {"count": n, "total_ns": ns, "by_tag": {}}
            for name, (n, ns) in totals.items()}


def _rec(tracer, stats_open, stats_close):
    """A run record as ``bench.run`` builds it for a traced run: a 10-s
    window of 100 steps; a 1000-ns traced window, the chip busy over
    [0, 400) and [600, 800), so idle over [400, 600) and [800, 1000)."""
    steps = [{"t0": 0.1 * i, "t_ready": 0.1 * i + 0.1} for i in range(100)]
    loop = types.SimpleNamespace(system=types.SimpleNamespace(
        metrics=types.SimpleNamespace(tracer=tracer)),
        stats_open=stats_open, stats_close=stats_close, steps=steps)
    ops = [("op", 0, "fusion", 0.0, 400.0), ("op", 0, "fusion", 600.0, 200.0)]
    return types.SimpleNamespace(
        cell=CELL, loop=loop, trace_span=(2.0, 2.000001),
        trace={"chips": 1, "ops": ops, "modules": []})


def test_readers_on_a_hand_built_record(out_dirs, monkeypatch):
    tracer = SpanTracer(cap=64)
    # perf_counter 2.0 s maps to profiler ns 0: background over [450, 550)
    # and [900, 1100) of the traced window
    tracer.push(ST_SCHED_TASK, 2_000_000_450, 100, 2)
    tracer.push(ST_SCHED_TASK, 2_000_000_900, 200, 2)
    tracer.push(ST_KV_APPEND, 2_000_000_100, 50)
    monkeypatch.setattr(_spans, "host_events", lambda rec: [
        ("host", -1, trace_reduce.WINDOW_SPAN, 0.0, 1000.0),
        ("host", -1, _spans.APPEND_SPAN, 90.0, 70.0)])
    opened = _stages(pin_step=(5, 1_000_000), sched_task=(10, 2_000_000))
    closed = _stages(pin_step=(15, 301_000_000), swap_in_alloc=(4, 40_000_000),
                     backend_load=(9, 150_000_000), kv_alloc=(20, 25_000_000),
                     sched_task=(500, 1_002_000_000))
    rec = _rec(tracer, {"stages": opened}, {"stages": closed})
    readers = metric_readers()
    got = {name: readers[name].read(rec) for name in NEW}
    assert got == pytest.approx({
        "pin_ms_per_request": 300.0 / 10,
        "pin_reclaim_ms_per_request": 40.0 / 10,
        "pin_decode_ms_per_request": 150.0 / 10,
        "kv_alloc_ms_per_step": 25.0 / 100,
        "background_busy_frac": 1.0 / 10.0,
        # idle 400 ns, of which [450, 550) and [900, 1000) background
        "idle_background_frac": 200.0 / 400.0})
    for name in NEW:
        assert readers[name].UNIT in ("ms", "fraction")
    assert _spans.traced(rec)["clock_check"] == {
        "spans": 1, "unmatched": 0, "max_overhang_us": 0.0}
    # a stage that saw no span in the window reads 0.0
    quiet = _rec(tracer, {"stages": closed}, {"stages": closed})
    for name in NEW[:5]:
        assert readers[name].read(quiet) == 0.0, name


def test_readers_return_none_with_the_tracer_off():
    rec = _rec(None, {"faults": 0}, {"faults": 0})
    readers = metric_readers()
    for name in NEW:
        assert readers[name].read(rec) is None, name
