"""GuestSpace -- the unified guest-memory surface (ISSUE 5).

Covers the API itself (alloc/free, bounds-checked I/O, typed views,
batched touch, pin), the observer protocol, the TraceRecorder capture
observer, the TaijiSystem deprecation shims (byte-equivalence + the
warning contract), the production rollout profile, and the
prefetch-exception satellite.
"""
import numpy as np
import pytest

from repro.core.config import small_test_config
from repro.core.elastic_kv import ElasticKVCache, KVGeometry, make_kv_taiji_config
from repro.core.guest import GuestObserver
from repro.core.system import TaijiSystem
from repro.core.virt import NO_PFN
from repro.fleet.controller import FleetConfig
from repro.fleet.trace import (OP_ALLOC, OP_FREE, OP_RDATA, OP_TICK,
                               OP_TOUCH, OP_WDATA, TraceRecorder,
                               decode_payload, parse_line)


@pytest.fixture
def system():
    s = TaijiSystem(small_test_config())
    yield s
    s.close()


class _Log(GuestObserver):
    def __init__(self):
        self.events = []

    def on_alloc(self, gfn):
        self.events.append(("alloc", gfn))

    def on_free(self, gfn):
        self.events.append(("free", gfn))

    def on_access(self, gfn, off, nbytes, is_write, data=None):
        self.events.append(("access", gfn, off, nbytes, is_write, data))

    def on_tick(self, rounds):
        self.events.append(("tick", rounds))


# ------------------------------------------------------------ core surface
def test_rw_roundtrip_and_bounds(system):
    space = system.guest
    g = space.alloc_ms()
    data = bytes(range(256)) * (system.cfg.mp_bytes // 256)
    space.write(g, data, off=system.cfg.mp_bytes)      # second MP
    assert space.read(g, len(data), off=system.cfg.mp_bytes) == data
    assert space.read(g, 5, off=system.cfg.mp_bytes) == data[:5]
    with pytest.raises(ValueError):
        space.write(g, b"x" * 8, off=system.cfg.ms_bytes - 4)
    with pytest.raises(ValueError):
        space.read(g, system.cfg.ms_bytes + 1)
    with pytest.raises(ValueError):
        space.read(g, 4, off=-1)
    # zero-length access at off == ms_bytes must NOT resolve (and fault)
    # the *next* MS -- the offset itself has to be inside this MS
    with pytest.raises(ValueError):
        space.write(g, b"", off=system.cfg.ms_bytes)
    with pytest.raises(ValueError):
        space.read(g, off=system.cfg.ms_bytes)
    space.free_ms(g)


def test_read_defaults_to_ms_end(system):
    space = system.guest
    g = space.alloc_ms()
    assert len(space.read(g)) == system.cfg.ms_bytes
    assert len(space.read(g, off=100)) == system.cfg.ms_bytes - 100


def test_typed_view_roundtrip(system):
    space = system.guest
    g = space.alloc_ms()
    view = space.view(g, np.float16, (4, 8), off=16)
    arr = np.arange(32, dtype=np.float16).reshape(4, 8)
    view.store(arr)
    np.testing.assert_array_equal(view.load(), arr)
    with pytest.raises(ValueError):
        view.store(np.zeros((3, 8), np.float16))
    with pytest.raises(ValueError):                     # view beyond the MS
        space.view(g, np.float64, (system.cfg.ms_bytes,))


def test_touch_faults_swapped_ms_back_in(system):
    space = system.guest
    g = space.alloc_ms()
    data = bytes([7]) * system.cfg.ms_bytes
    space.write(g, data)
    system.engine.swap_out_ms(g)
    assert int(system.virt.table.pfn[g]) == NO_PFN
    assert space.touch([g]) == 1
    assert int(system.virt.table.pfn[g]) != NO_PFN
    assert space.touch([g]) == 0                        # already resident
    assert space.read(g) == data


def test_pin_context(system):
    space = system.guest
    g = space.alloc_ms()
    system.engine.swap_out_ms(g)
    with space.pin([g]):
        assert system.virt.table.is_pinned(g)
        assert int(system.virt.table.pfn[g]) != NO_PFN
    assert not system.virt.table.is_pinned(g)


def test_residency_counts(system):
    space = system.guest
    gfns = [space.alloc_ms() for _ in range(3)]
    space.write(gfns[0], b"\x01" * system.cfg.ms_bytes)
    system.engine.swap_out_ms(gfns[0])
    res = space.residency(gfns)
    assert res == {"resident": 2, "swapped": 1, "total": 3}


# -------------------------------------------------------------- observers
def test_observer_sees_alloc_access_free_tick(system):
    space = system.guest
    log = space.attach(_Log())
    g = space.alloc_ms()
    space.write(g, b"abcd", off=8)
    space.read(g, 4, off=8)
    space.hint_accessed([g])
    space.step_background()
    space.free_ms(g)
    space.detach(log)
    space.alloc_ms()                                    # not observed
    assert log.events == [
        ("alloc", g),
        ("access", g, 8, 4, True, b"abcd"),
        ("access", g, 8, 4, False, b"abcd"),
        ("access", g, 0, 0, False, None),
        ("tick", 1),
        ("free", g),
    ]


def test_system_guest_is_canonical(system):
    assert system.guest is system.guest
    cache = ElasticKVCache(
        KVGeometry(n_layers=1, kv_heads=1, head_dim=16, block_tokens=4),
        system)
    assert cache.space is system.guest


# ------------------------------------------------------ deprecation shims
def test_shims_warn_and_stay_byte_equivalent(system):
    space = system.guest
    g = space.alloc_ms()
    data = b"taiji-shim" * 3
    with pytest.warns(DeprecationWarning):
        addr = system.ms_addr(g, mp=1, off=4)
    assert addr == space.addr_of(g, mp=1, off=4)
    with pytest.warns(DeprecationWarning):
        system.write(addr, data)
    assert space.read(g, len(data), off=system.cfg.mp_bytes + 4) == data
    with pytest.warns(DeprecationWarning):
        got = system.read(addr, len(data))
    assert got == data


def test_shims_flow_through_guest_observers(system):
    """Shimmed accesses are visible to GuestSpace observers -- the shim
    delegates through the canonical space, not around it."""
    space = system.guest
    g = space.alloc_ms()
    log = space.attach(_Log())
    with pytest.warns(DeprecationWarning):
        system.write(space.addr_of(g, off=32), b"zz")
    assert log.events == [("access", g, 32, 2, True, b"zz")]


# ---------------------------------------------------------- TraceRecorder
def test_trace_recorder_emits_replayable_ops(system):
    space = system.guest
    pre = space.alloc_ms()                  # allocated before capture
    rec = space.attach(TraceRecorder.for_space(space))
    g = space.alloc_ms()
    space.write(g, b"\x05" * 64, off=128)
    space.read(g, 64, off=128)
    space.touch([g])
    space.step_background(2)
    space.write(pre, b"\x06" * 8)           # lazily registers `pre`
    space.free_ms(g)
    lines = rec.lines()
    parsed = [parse_line(ln) for ln in lines[1:]]
    ops = [(op, arg, w) for _seq, op, arg, w, _p in parsed]
    ms = system.cfg.ms_bytes
    assert ops == [
        (OP_ALLOC, 0, 0),
        (OP_WDATA, 0 * ms + 128, 1),
        (OP_RDATA, 0 * ms + 128, 0),
        (OP_TOUCH, 0 * ms, 0),
        (OP_TICK, 2, 0),
        (OP_ALLOC, 1, 0),                   # pre-capture MS, lazy token
        (OP_WDATA, 1 * ms, 1),
        (OP_FREE, 0, 0),
    ]
    assert decode_payload(parsed[1][4]) == b"\x05" * 64
    import zlib
    crc = zlib.crc32(b"\x05" * 64) & 0xFFFFFFFF
    assert parsed[2][4] == f"64:{crc:08x}"


def test_trace_recorder_reestablishes_precapture_content(system):
    """A read of pre-capture content (an MS that existed before the
    recorder attached) must first emit a wdata carrying the observed
    bytes -- a replay starts from a zeroed MS and cannot know them --
    so the rdata content check passes at replay."""
    space = system.guest
    pre = space.alloc_ms()
    payload = bytes(range(200, 256)) * 4                 # 224 bytes
    space.write(pre, payload, off=64)
    rec = space.attach(TraceRecorder.for_space(space))
    space.read(pre, len(payload), off=64)                # pre-capture bytes
    space.write(pre, b"\x11" * 16, off=64)               # now covered
    space.read(pre, 16, off=64)                          # no re-establish
    ops = [parse_line(ln) for ln in rec.lines()[1:]]
    kinds = [op for _s, op, _a, _w, _p in ops]
    assert kinds == [OP_ALLOC, OP_WDATA, OP_RDATA, OP_WDATA, OP_RDATA]
    # the first wdata is the synthesized re-establishment of what the
    # read observed, at the read's own address
    assert ops[1][2] == ops[2][2] == 0 * system.cfg.ms_bytes + 64
    assert decode_payload(ops[1][4]) == payload
    # gaps are per-range: the second read's range was written, so no
    # extra wdata precedes the second rdata


def test_trace_recorder_coverage_gap_splitting(system):
    """Partial coverage: only the unwritten subranges of a read are
    re-established, in order, with the observed bytes."""
    space = system.guest
    pre = space.alloc_ms()
    space.write(pre, b"\xAA" * 256)                      # [0, 256) content
    rec = space.attach(TraceRecorder.for_space(space))
    space.write(pre, b"\xBB" * 32, off=64)               # covers [64, 96)
    space.read(pre, 192)                                 # [0, 192)
    ops = [parse_line(ln) for ln in rec.lines()[1:]]
    kinds = [op for _s, op, _a, _w, _p in ops]
    assert kinds == [OP_ALLOC, OP_WDATA, OP_WDATA, OP_WDATA, OP_RDATA]
    # gap wdatas: [0, 64) then [96, 192), around the recorded write
    assert ops[2][2] % system.cfg.ms_bytes == 0
    assert decode_payload(ops[2][4]) == b"\xAA" * 64
    assert ops[3][2] % system.cfg.ms_bytes == 96
    assert decode_payload(ops[3][4]) == b"\xAA" * 96


# ------------------------------------------------------ rollout profile
def test_production_profile_wires_latency_guard():
    prof = FleetConfig.production_profile()
    assert prof.latency_guard_factor is not None
    assert prof.latency_guard_factor > 1.0
    assert prof.latency_guard_min_samples >= FleetConfig().latency_guard_min_samples
    assert prof.reclaim_stagger_groups >= 2
    # the profile is a plain FleetConfig: a fleet built from it runs the
    # guard path on every upgrade batch (exercised in test_fleet.py's
    # latency-guard tests); here we pin the wiring contract
    assert prof.overcommit_cap == pytest.approx(1.25)


# ------------------------------------------------- prefetch exceptions
def test_prefetch_async_surfaces_worker_exception():
    geom = KVGeometry(n_layers=1, kv_heads=1, head_dim=16, block_tokens=4)
    cfg = make_kv_taiji_config(geom, 8, overcommit=1.0)
    s = TaijiSystem(cfg)
    try:
        cache = ElasticKVCache(geom, s)
        cache.create_sequence(0)
        for _ in range(4):
            cache.append_kv(0, np.zeros((1, 2, 1, 16), np.float16))
        s.engine.swap_out_ms(cache.blocks_of(0)[0])

        boom = RuntimeError("prefetch exploded")

        def bad_swap_in(gfn, **kw):
            raise boom

        s.engine.swap_in_ms = bad_swap_in
        th = cache.prefetch_async([0])
        with pytest.raises(RuntimeError, match="prefetch exploded"):
            th.join(timeout=5)
        assert th.exc is boom
    finally:
        s.close()


def test_prefetch_async_clean_join():
    geom = KVGeometry(n_layers=1, kv_heads=1, head_dim=16, block_tokens=4)
    cfg = make_kv_taiji_config(geom, 8, overcommit=1.0)
    s = TaijiSystem(cfg)
    try:
        cache = ElasticKVCache(geom, s)
        cache.create_sequence(0)
        for _ in range(4):
            cache.append_kv(0, np.zeros((1, 2, 1, 16), np.float16))
        s.engine.swap_out_ms(cache.blocks_of(0)[0])
        th = cache.prefetch_async([0])
        th.join(timeout=5)                  # no exception to surface
        assert th.exc is None
    finally:
        s.close()


# --------------------------------- batch ops vs. the faults they trigger
@pytest.mark.parametrize("op", ["gather", "read_many"])
def test_batch_read_survives_reclaim_by_its_own_fault(op):
    """A swapped row faulting in with memory exhausted reclaims other
    MSs -- possibly rows of the same batch already probed as resident.
    Their frames then hold the faulted MS, so each resident row must be
    copied before the first fault."""
    s = TaijiSystem(small_test_config(n_phys_ms=12, mpool_reserve_ms=2))
    try:
        space, cfg = s.guest, s.cfg
        gfns, data = [], {}
        while s.phys.free_count:
            g = space.alloc_ms()
            data[g] = bytes([len(gfns) + 1]) * cfg.ms_bytes
            space.write(g, data[g])
            gfns.append(g)
        # the oldest MS goes out; a new one takes its frame, so the
        # fault below must reclaim resident MSs of the batch
        s.engine.swap_out_ms(gfns[0])
        g = space.alloc_ms()
        data[g] = b"\xee" * cfg.ms_bytes
        space.write(g, data[g])
        batch = gfns[:6]
        if op == "gather":
            got = [row.tobytes() for row in space.gather(batch)]
        else:
            got = space.read_many([(g, 0, cfg.ms_bytes) for g in batch])
        assert got == [data[g] for g in batch]
        assert s.metrics.ms_swapped_out > 1          # the fault reclaimed
    finally:
        s.close()
