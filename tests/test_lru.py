"""Multi-level LRU (paper §4.2.1, Fig 7): transitions, smoothing, order."""
import random

import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - CI pins hypothesis
    HAVE_HYPOTHESIS = False

from repro.core.config import small_test_config
from repro.core.lru import (ACTIVE, COLD, HOT, HOT_INT, INACTIVE,
                            MultiLevelLRU)


class Bits:
    def __init__(self):
        self.accessed = set()

    def probe(self, gfn):
        hit = gfn in self.accessed
        self.accessed.discard(gfn)
        return hit


def make(stabilize=1):
    cfg = small_test_config(lru=small_test_config().lru.__class__(
        scan_interval_s=0.001, stabilize_scans=stabilize, workers=1,
        scan_cache_size=4))
    bits = Bits()
    return MultiLevelLRU(cfg, bits.probe), bits


def test_access_moves_toward_hot_one_level_per_scan():
    lru, bits = make()
    lru.track(1)                       # starts ACTIVE
    assert lru.level_of(1) == ACTIVE
    bits.accessed.add(1)
    lru.scan_shard(0, 1)
    assert lru.level_of(1) == HOT_INT  # one level only (smoothing)
    bits.accessed.add(1)
    lru.scan_shard(0, 1)
    assert lru.level_of(1) == HOT


def test_idle_drifts_toward_cold_with_stabilization():
    lru, bits = make(stabilize=2)
    lru.track(7)
    lru.scan_shard(0, 1)               # 1 idle scan: no move yet
    assert lru.level_of(7) == ACTIVE
    lru.scan_shard(0, 1)               # 2nd idle scan: move one level
    assert lru.level_of(7) == INACTIVE
    for _ in range(4):
        lru.scan_shard(0, 1)
    assert lru.level_of(7) == COLD


def test_transient_access_does_not_jump_to_hot():
    """A single access inside a huge page must not look permanently hot."""
    lru, bits = make(stabilize=1)
    lru.track(3)
    bits.accessed.add(3)
    lru.scan_shard(0, 1)
    assert lru.level_of(3) == HOT_INT
    for _ in range(6):                 # goes cold again when idle
        lru.scan_shard(0, 1)
    assert lru.level_of(3) == COLD


def test_pick_cold_orders_coldest_first():
    lru, bits = make(stabilize=1)
    for g in (10, 11, 12):
        lru.track(g)
    for _ in range(5):
        lru.scan_shard(0, 1)
    assert lru.level_of(10) == COLD
    picked = lru.pick_cold(2)
    assert picked == [10, 11]          # arrival order = coldest first


@pytest.mark.parametrize("pick", ["pick_cold", "pick_coldest_any"])
def test_pickers_pass_over_skipped_pages(pick):
    """Exhausted-memory reclaim skips pinned MSs; it must still get the
    next candidates, not the pinned ones again."""
    lru, bits = make(stabilize=1)
    for g in (10, 11, 12, 13):
        lru.track(g)
    for _ in range(5):
        lru.scan_shard(0, 1)
    picked = getattr(lru, pick)(2, skip=lambda g: g in (10, 12))
    assert picked == [11, 13]


def test_swapin_joins_hot_set():
    lru, bits = make()
    lru.track(5)
    lru.note_swapped_out(5)
    assert lru.level_of(5) is None
    lru.note_swapped_in(5)
    assert lru.level_of(5) == HOT


def _run_traffic(ops):
    """Shared property body: hypothesis and the seeded fallback drive it."""
    lru, bits = make()
    tracked = set()
    for gfn, access in ops:
        if gfn not in tracked:
            lru.track(gfn)
            tracked.add(gfn)
        if access:
            bits.accessed.add(gfn)
        lru.scan_shard(0, 1)
        lru.check_invariants()
    assert lru.tracked() == len(tracked)
    counts = lru.counts()
    assert sum(counts.values()) == len(tracked)


if HAVE_HYPOTHESIS:
    @given(st.lists(st.tuples(st.integers(0, 15), st.booleans()),
                    max_size=120))
    @settings(max_examples=40, deadline=None)
    def test_invariants_under_random_traffic(ops):
        _run_traffic(ops)


def test_invariants_under_seeded_random_traffic():
    """Seeded-``random`` fallback fuzz: randomized coverage without
    hypothesis (not installed in the local container; CI keeps the
    hypothesis path above)."""
    rng = random.Random(0x7A111)
    for _case in range(40):
        n_ops = rng.randrange(0, 121)
        ops = [(rng.randrange(0, 16), rng.random() < 0.5)
               for _ in range(n_ops)]
        _run_traffic(ops)
