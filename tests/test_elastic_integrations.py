"""Elastic KV cache + elastic expert cache over the Taiji core."""
import numpy as np
import pytest

pytestmark = pytest.mark.slow      # excluded from the default CI lane

from repro.core.config import LRUConfig
from repro.core.elastic_kv import ElasticKVCache, KVGeometry, make_kv_taiji_config
from repro.core.elastic_params import ElasticExpertCache, make_expert_taiji_config
from repro.core.system import TaijiSystem

GEOM = KVGeometry(n_layers=2, kv_heads=2, head_dim=16, block_tokens=4)


def make_cache(phys_blocks=8, overcommit=2.0):
    cfg = make_kv_taiji_config(GEOM, phys_blocks, overcommit=overcommit,
                               lru=LRUConfig(scan_interval_s=0.001,
                                             stabilize_scans=1, workers=1))
    system = TaijiSystem(cfg)
    return ElasticKVCache(GEOM, system), system


def test_kv_roundtrip_exact_under_pressure():
    cache, system = make_cache(phys_blocks=6)
    rng = np.random.default_rng(0)
    mirror = {}
    n_seqs, toks = 6, 12                  # 6 seqs x 3 blocks = 18 > 6 phys
    for sid in range(n_seqs):
        cache.create_sequence(sid)
        mirror[sid] = []
        for _ in range(toks):
            kv = rng.standard_normal((2, 2, 2, 16)).astype(np.float16)
            cache.append_kv(sid, kv)
            mirror[sid].append(kv)
    res = cache.residency()
    assert res["total_blocks"] == n_seqs * (toks // GEOM.block_tokens)
    assert res["swapped_blocks"] > 0      # pressure forced swaps
    for sid in range(n_seqs):
        for b in range(toks // GEOM.block_tokens):
            got = cache.read_block(sid, b)
            want = np.stack(mirror[sid][b * 4 : (b + 1) * 4])
            np.testing.assert_array_equal(got, want.astype(np.float16))
    system.close()


def test_prepare_step_pins_and_faults_in():
    cache, system = make_cache(phys_blocks=6)
    rng = np.random.default_rng(1)
    for sid in range(6):
        cache.create_sequence(sid)
        for _ in range(8):
            cache.append_kv(sid, rng.standard_normal((2, 2, 2, 16)).astype(np.float16))
    # force seq 0 out
    for g in cache.blocks_of(0):
        system.engine.swap_out_ms(g)
    with cache.prepare_step([0]):
        for g in cache.blocks_of(0):
            assert system.virt.table.is_pinned(g)
            assert int(system.virt.table.pfn[g]) != -1
    for g in cache.blocks_of(0):
        assert not system.virt.table.is_pinned(g)
    system.close()


def test_drop_sequence_frees_memory():
    cache, system = make_cache(phys_blocks=6)
    rng = np.random.default_rng(2)
    cache.create_sequence(0)
    for _ in range(8):
        cache.append_kv(0, rng.standard_normal((2, 2, 2, 16)).astype(np.float16))
    free_before = system.phys.free_count
    cache.drop_sequence(0)
    assert system.phys.free_count > free_before
    system.close()


def test_expert_cache_residency_follows_routing():
    n_experts, hot = 8, 3
    shape = (64, 32)
    cfg = make_expert_taiji_config(
        int(np.prod(shape)) * 4, hot, n_experts,
        lru=LRUConfig(scan_interval_s=0.001, stabilize_scans=1, workers=1))
    system = TaijiSystem(cfg)
    cache = ElasticExpertCache(system, n_experts, shape, dtype=np.float32)
    rng = np.random.default_rng(3)
    weights = {e: rng.standard_normal(shape).astype(np.float32)
               for e in range(n_experts)}
    for e, w in weights.items():
        cache.put_expert(e, w)

    # router loves experts 0..2
    for _ in range(10):
        cache.note_routing([0, 1, 2])
        for _ in range(3):
            system.lru.scan_shard(0, 1)
        system.engine.reclaim_round()

    # all experts still readable and exact (swapped ones fault back in)
    for e, w in weights.items():
        np.testing.assert_array_equal(cache.get_expert(e), w)

    # dispatch pinning works for a cold expert
    with cache.prepare_dispatch([5]):
        gfn = cache._view[5].gfn
        assert system.virt.table.is_pinned(gfn)
    system.close()
