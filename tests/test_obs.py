"""Stage-attributed tracing (repro.obs): span tracer mechanics, stage
tree rollup, Chrome-trace export schema, Prometheus rendering, and -- the
contract that makes tracing deployable -- deterministic snapshots stay
byte-identical with tracing on (single box, fleet replay, and chaos).
"""
import json

import numpy as np
import pytest

from repro.core.config import ObsConfig, small_test_config
from repro.core.elastic_kv import (ElasticKVCache, KVGeometry,
                                   make_kv_taiji_config)
from repro.core.metrics import FK_COMPRESSED, FK_NAMES, FK_ZERO, Metrics
from repro.core.system import TaijiSystem
from repro.fleet import chaos_trace, paper_trace
from repro.fleet.harness import build_fleet, replay_twice
from repro.obs import (STAGE_NAMES, SpanTracer, export_chrome, render_prom,
                       stage_tree)
from repro.obs.tracer import (ST_FAULT_MUTEX, ST_FAULT_TOTAL,
                              ST_GUEST_ACCESS, ST_KV_APPEND, ST_PIN_STEP,
                              ST_SCHED_TASK, STAGES, TAG_READ, TAG_SWAPIN_PIN)


def traced_cfg(**overrides):
    return small_test_config(obs=ObsConfig(enabled=True), **overrides)


def zero_fault_workload(system):
    """Alloc one MS, swap every (zero) MP out, fault each back with one
    read. Returns (gfn, n_reads)."""
    cfg = system.cfg
    space = system.guest
    g = space.alloc_ms()
    assert system.engine.swap_out_ms(g) == cfg.mps_per_ms
    for mp in range(cfg.mps_per_ms):
        assert space.read(g, 16, off=mp * cfg.mp_bytes) == bytes(16)
    return g, cfg.mps_per_ms


# ---------------------------------------------------------- tracer unit
def test_push_flush_aggregates():
    tr = SpanTracer(cap=64)
    for i in range(10):
        tr.push(ST_FAULT_TOTAL, 1000 + i, 100 + i, FK_ZERO)
    tr.flush()
    t = tr.totals()["fault_total"]
    assert t["count"] == 10
    assert t["total_ns"] == sum(100 + i for i in range(10))
    assert t["max_ns"] == 109
    assert t["by_tag"][FK_ZERO]["count"] == 10


def test_ring_overflow_auto_flushes():
    tr = SpanTracer(cap=8)
    for i in range(100):
        tr.push(ST_GUEST_ACCESS, i, 5, TAG_READ)
    assert tr.span_count == 100          # nothing lost: push flushes at cap


def test_max_spans_bounds_retained_not_aggregates():
    tr = SpanTracer(cap=64, max_spans=5)
    for i in range(12):
        tr.push(ST_GUEST_ACCESS, i, 7)
    tr.flush()
    assert tr.span_count == 12           # aggregates never drop
    assert len(list(tr.spans())) == 5    # retained store is bounded
    assert tr.dropped_spans == 7


def test_retained_store_keeps_the_newest_spans():
    tr = SpanTracer(cap=4, max_spans=6)
    for i in range(15):                  # flushes at 4, 8, 12 and below
        tr.push(ST_GUEST_ACCESS, 1000 + i, 7)
    tr.flush()
    assert tr.span_count == 15           # aggregates never drop
    _, t0, dur, _, _ = tr.span_arrays()
    assert t0.tolist() == [1000 + i for i in range(9, 15)]
    assert [s[1] for s in tr.spans()] == t0.tolist()
    assert tr.dropped_spans == 9 and set(dur.tolist()) == {7}


def test_zero_duration_span_survives_flush():
    # enc uses dur+1 so a 0ns span is not mistaken for an empty slot
    tr = SpanTracer(cap=8)
    tr.push(ST_FAULT_MUTEX, 123, 0)
    tr.flush()
    t = tr.totals()["fault_mutex"]
    assert t["count"] == 1 and t["total_ns"] == 0


def test_stage_tree_self_time_rollup():
    tr = SpanTracer(cap=64)
    tr.push(ST_FAULT_TOTAL, 0, 100_000)
    tr.push(ST_FAULT_MUTEX, 0, 30_000)
    tree = stage_tree([tr])
    assert tree["fault_total"]["self_ns"] == 70_000
    assert tree["fault_mutex"]["self_ns"] == 30_000
    assert tree["fault_mutex"]["parent"] == "fault_total"


def test_stage_tree_self_time_clamps_at_zero():
    tr = SpanTracer(cap=64)
    tr.push(ST_FAULT_TOTAL, 0, 10_000)
    tr.push(ST_FAULT_MUTEX, 0, 40_000)   # child exceeds parent (fan-out)
    assert stage_tree([tr])["fault_total"]["self_ns"] == 0


def test_stage_tree_aggregates_across_tracers():
    a, b = SpanTracer(cap=8), SpanTracer(cap=8)
    a.push(ST_FAULT_TOTAL, 0, 100)
    b.push(ST_FAULT_TOTAL, 0, 300)
    t = stage_tree([a, b])["fault_total"]
    assert t["count"] == 2 and t["total_ns"] == 400 and t["max_ns"] == 300


# ----------------------------------------------------- system integration
def test_tracer_disabled_by_default():
    s = TaijiSystem(small_test_config())
    try:
        assert s.tracer is None
        assert s.metrics.tracer is None
    finally:
        s.close()


def test_span_counts_match_call_counts():
    s = TaijiSystem(traced_cfg())
    try:
        _, n_reads = zero_fault_workload(s)
        tr = s.tracer
        # every read is one guest_access span; every swapped MP is one
        # fault_total span with the same interval the fault ring records
        assert tr.stage_count("guest_access") == n_reads
        assert tr.stage_count("fault_total") == n_reads
        assert s.metrics.faults == n_reads
        assert tr.stage_count("swap_out") == 1
    finally:
        s.close()


def test_fault_subtree_telescopes_to_fault_total():
    """The fault_total span shares the fault ring's interval, so the
    fault subtree's self-times must sum exactly to fault_total's total --
    the invariant behind the fleet_swapin_stage_* BENCH rows."""
    s = TaijiSystem(traced_cfg())
    try:
        space = s.guest
        g = space.alloc_ms()
        pat = bytes(range(256)) * (s.cfg.mp_bytes // 256)
        for mp in range(s.cfg.mps_per_ms):
            space.write(g, pat, off=mp * s.cfg.mp_bytes)
        s.engine.swap_out_ms(g)
        for mp in range(s.cfg.mps_per_ms):
            space.read(g, 16, off=mp * s.cfg.mp_bytes)
        tree = stage_tree([s.tracer])
        subtree = ("fault_total", "fault_mutex", "fault_desc", "fault_alloc",
                   "fault_copy", "fault_backend", "fault_readahead",
                   "readahead_decode")
        self_sum = sum(tree[n]["self_ns"] for n in subtree if n in tree)
        assert self_sum == tree["fault_total"]["total_ns"]
    finally:
        s.close()


KV_GEOM = KVGeometry(n_layers=2, kv_heads=2, head_dim=16, block_tokens=4)


def _inside(inner, outer, slack_ns=0):
    """Each (t0, dur) of ``inner`` lies within one span of ``outer``."""
    lo = np.array([t for t, _ in outer])
    hi = lo + np.array([d for _, d in outer])
    return all(np.any((lo - slack_ns <= t) & (t + d <= hi + slack_ns))
               for t, d in inner)


def test_serving_config_traces_by_default():
    assert make_kv_taiji_config(KV_GEOM, 6).obs.enabled
    off = make_kv_taiji_config(KV_GEOM, 6, obs=ObsConfig())
    assert not off.obs.enabled           # an explicit obs= wins
    assert not small_test_config().obs.enabled


def test_served_path_stages_nest_and_partition():
    """Six KV sequences of three blocks over six physical blocks: appends
    open blocks by reclaiming synchronously (kv_alloc), and pinning each
    sequence swaps its blocks back in, reclaiming inside the swap-in's
    slot allocation (swap_in_alloc). The pin_step and kv_append subtrees'
    self-times add up to their roots' totals, and each new span lies
    inside one span of its declared parent."""
    system = TaijiSystem(make_kv_taiji_config(KV_GEOM, 6, overcommit=2.0))
    try:
        cache = ElasticKVCache(KV_GEOM, system)
        rng = np.random.default_rng(0)
        for sid in range(6):
            cache.create_sequence(sid)
            for _ in range(12):
                cache.append_kv(sid, rng.standard_normal(
                    (2, 2, 2, 16)).astype(np.float16))
        for sid in range(6):
            with cache.prepare_step([sid]):
                pass
        tree = stage_tree([system.tracer])
        assert tree["pin_step"]["count"] == 6
        assert tree["kv_append"]["count"] == 6 * 12
        assert tree["kv_alloc"]["count"] == 6 * 3
        for name in ("swap_in_lock", "swap_in", "swap_in_alloc",
                     "backend_load", "swap_out"):
            assert tree[name]["count"] > 0, name
        assert tree["swap_in"]["by_tag"].keys() == {TAG_SWAPIN_PIN}
        parent = dict(STAGES)

        def lineage(name):
            while name is not None:
                yield name
                name = parent[name]

        for root in ("pin_step", "kv_append"):
            sub = [n for n in tree if root in lineage(n)]
            assert sum(tree[n]["self_ns"] for n in sub) == \
                tree[root]["total_ns"], root
        stage, t0, dur, _, _ = system.tracer.span_arrays()

        def spans(name):
            sel = stage == STAGE_NAMES.index(name)
            return list(zip(t0[sel].tolist(), dur[sel].tolist()))

        for child in ("swap_in_lock", "swap_in", "swap_in_alloc",
                      "backend_load", "kv_alloc"):
            assert _inside(spans(child), spans(parent[child])), child
        # synchronous reclaim ran inside both allocations
        outs = spans("swap_out")
        assert any(_inside([s], spans("kv_alloc")) for s in outs)
        assert any(_inside([s], spans("swap_in_alloc")) for s in outs)
    finally:
        system.close()


def test_snapshot_stage_deltas():
    m = Metrics()
    assert "stages" not in m.snapshot()           # no tracer, no stages
    m.tracer = SpanTracer(cap=8)
    m.tracer.push(ST_PIN_STEP, 0, 500)
    a = m.snapshot()["stages"]
    for i in range(20):                           # crosses ring flushes
        m.tracer.push(ST_PIN_STEP, 10 + i, 100 + i)
        m.tracer.push(ST_KV_APPEND, 10 + i, 3, i % 2)
    m.tracer.push(ST_SCHED_TASK, 50, 40, 2)
    b = m.snapshot()["stages"]
    assert a == {"pin_step": {"count": 1, "total_ns": 500,
                              "by_tag": {0: {"count": 1, "total_ns": 500}}}}
    assert b["pin_step"]["count"] - a["pin_step"]["count"] == 20
    assert b["pin_step"]["total_ns"] - a["pin_step"]["total_ns"] == \
        sum(100 + i for i in range(20))
    assert b["kv_append"]["by_tag"] == {0: {"count": 10, "total_ns": 30},
                                        1: {"count": 10, "total_ns": 30}}
    assert b["sched_task"] == {"count": 1, "total_ns": 40,
                               "by_tag": {2: {"count": 1, "total_ns": 40}}}


# --------------------------------------------------------- chrome export
def test_chrome_export_schema(tmp_path):
    s = TaijiSystem(traced_cfg())
    try:
        zero_fault_workload(s)
        path = tmp_path / "trace.json"
        n = s.tracer.export_chrome(str(path))
        assert n > 0
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ns"
        events = doc["traceEvents"]
        assert len(events) == n
        last_ts = 0.0
        for ev in events:
            assert set(ev) >= {"name", "cat", "ph", "ts", "dur",
                               "pid", "tid"}
            assert ev["ph"] == "X"
            assert ev["name"] in STAGE_NAMES
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert ev["ts"] >= last_ts   # sorted by timestamp
            last_ts = ev["ts"]
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    finally:
        s.close()


def test_chrome_export_merges_tracers_with_pids(tmp_path):
    a, b = SpanTracer(cap=8, pid=0), SpanTracer(cap=8, pid=3)
    a.push(ST_FAULT_TOTAL, 100, 10)
    b.push(ST_FAULT_TOTAL, 200, 10)
    path = tmp_path / "t.json"
    assert export_chrome(str(path), [a, b]) == 2
    pids = {ev["pid"] for ev in json.loads(path.read_text())["traceEvents"]}
    assert pids == {0, 3}


# ------------------------------------------------------------ prometheus
def test_render_prom_counters_and_histograms():
    s = TaijiSystem(traced_cfg())
    try:
        zero_fault_workload(s)
        text = s.metrics.render_prom()
        assert text.endswith("\n")
        assert f"taiji_faults_total {s.metrics.faults}" in text
        assert "taiji_fault_latency_seconds_count" in text
        assert 'le="+Inf"' in text
        assert "taiji_compression_ratio" in text
        # tracer stages render when tracing is on
        assert 'taiji_stage_spans_total{stage="fault_total"}' in text
        # per-kind labeled series
        assert 'kind="zero"' in text
    finally:
        s.close()


def test_render_prom_without_tracer():
    m = Metrics()
    m.faults = 3
    text = render_prom(m)
    assert "taiji_faults_total 3" in text
    assert "stage_spans_total" not in text


# ---------------------------------------------- per-kind histogram identity
def test_fault_kind_histograms_distinct_after_flush():
    """Regression: the per-kind histograms behind fault_zero_p90_us /
    fault_readahead_p90_us / fault_latency_p99 are distinct objects fed
    distinct samples -- equal reported percentiles are order statistics
    landing on the same sample, not aliased state."""
    m = Metrics()
    m.fault_ring.push(1000, FK_ZERO)
    m.fault_ring.push(5000, FK_COMPRESSED)
    m.sync()
    kinds = m.fault_latency_by_kind
    objs = [kinds[name] for name in FK_NAMES]
    assert len({id(h) for h in objs}) == len(objs)
    assert id(m.fault_latency) not in {id(h) for h in objs}
    assert kinds["zero"].count == 1 and kinds["compressed"].count == 1
    assert kinds["zero"].total_ns == 1000
    assert kinds["compressed"].total_ns == 5000
    # reset rebuilds fresh objects; captured references keep their samples
    captured = dict(kinds)
    m.reset_fault_latency()
    fresh = m.fault_latency_by_kind
    for name in FK_NAMES:
        assert fresh[name] is not captured[name]
        assert fresh[name].count == 0
    assert captured["zero"].count == 1   # window-frozen, not cleared


# ----------------------------------------------------------- determinism
def test_deterministic_snapshot_identical_traced_vs_untraced():
    snaps = []
    for cfg in (small_test_config(), traced_cfg()):
        s = TaijiSystem(cfg)
        try:
            zero_fault_workload(s)
            snaps.append(json.dumps(s.metrics.deterministic_snapshot(),
                                    sort_keys=True))
        finally:
            s.close()
    assert snaps[0] == snaps[1]


def test_fleet_replay_deterministic_with_tracing():
    cfg = traced_cfg()
    gen = paper_trace(7, cfg.ms_bytes, cfg.mps_per_ms, fill_ms=40,
                      burst=120, churn_frees=6)
    fleets = []

    def make_fleet():
        fleet = build_fleet(4, 2, cfg)
        fleets.append(fleet)
        return fleet

    eq = replay_twice(gen.lines(), make_fleet=make_fleet)
    assert eq.identical, eq.report()
    # tracers recorded real spans and survive the harness's fleet.close()
    tracers = [n.system.metrics.tracer for n in fleets[0].nodes]
    assert all(tr is not None for tr in tracers)
    assert sum(tr.span_count for tr in tracers) > 0
    assert fleets[0].tracer is not None
    assert fleets[0].tracer.stage_count("fleet_tick") > 0


def test_fleet_traced_bytes_equal_untraced_bytes():
    """Tracing must not leak into the deterministic snapshot: the same
    seeded trace replayed traced and untraced serializes identically."""
    runs = {}
    for name, cfg in (("off", small_test_config()), ("on", traced_cfg())):
        gen = paper_trace(7, cfg.ms_bytes, cfg.mps_per_ms, fill_ms=30,
                          burst=80, churn_frees=4)
        eq = replay_twice(gen.lines(), n_nodes=2, domains=2, cfg=cfg)
        assert eq.identical, eq.report()
        runs[name] = eq.runs[0].bytes
    assert runs["on"] == runs["off"]


@pytest.mark.slow
def test_fleet_chaos_deterministic_with_tracing():
    cfg = traced_cfg()
    managed = 4 * (cfg.n_phys_ms - cfg.mpool_reserve_ms)
    gen = chaos_trace(13, cfg.ms_bytes, cfg.mps_per_ms, 4,
                      fill_ms=int(managed * 1.1), burst=200,
                      kills=2, migrations=3)
    eq = replay_twice(gen.lines(), n_nodes=4, domains=2, cfg=cfg)
    assert eq.identical, eq.report()
