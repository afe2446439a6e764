"""Distributed range-partitioned store: correctness on a local mesh, and
the durable ShardedStore lifecycle (kill → reopen from shard directories →
serve through the shard_map path).  Run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (scripts/ci.sh) to
exercise the real multi-device mesh; on one device the mesh tests fall
back to n_shards=1 or skip."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LSMConfig, StoreConfig
from repro.core.datasets import make_dataset
from repro.core.distributed import (KEY_SENTINEL, DistStoreConfig,
                                    build_dist_get, build_dist_state,
                                    build_dist_state_from_shards,
                                    dist_get_local)
from repro.core.engine import EngineConfig
from repro.distributed import ShardedConfig, ShardedStore, load_shard_snapshot


def test_local_shard_lookup():
    keys = make_dataset("osm", 4096, seed=3)
    vptrs = np.arange(4096, dtype=np.int64)
    cfg = DistStoreConfig(n_keys=4096, probe_batch=256)
    state = build_dist_state(keys, vptrs, n_shards=4, cfg=cfg)
    rng = np.random.default_rng(0)
    probes = jnp.asarray(rng.choice(keys, 256))
    # probe each shard; union of hits must cover every probe exactly once
    hits = np.zeros(256, np.int32)
    vals = np.zeros(256, np.int64)
    for s in range(4):
        shard = {k: jnp.asarray(v[s: s + 1]) for k, v in state.items()}
        h, v = dist_get_local(shard, probes, cfg.delta)
        hits += np.asarray(h, np.int32)
        vals += np.where(np.asarray(h), np.asarray(v), 0)
    assert (hits == 1).all()
    np.testing.assert_array_equal(
        vals, np.searchsorted(keys, np.asarray(probes)))


def test_dist_get_shardmap_single_device():
    """shard_map path on the 1-device CPU mesh (n_shards=1)."""
    keys = make_dataset("ar", 2048, seed=5)
    vptrs = np.arange(2048, dtype=np.int64)
    cfg = DistStoreConfig(n_keys=2048, probe_batch=128)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Explicit,))
    state_np = build_dist_state(keys, vptrs, n_shards=1, cfg=cfg)
    state = {k: jnp.asarray(v) for k, v in state_np.items()}
    fn = build_dist_get(mesh, cfg)
    rng = np.random.default_rng(1)
    pos = rng.choice(keys, 64)
    neg = pos + 1
    probes = jnp.asarray(np.concatenate([pos, neg]))
    with jax.set_mesh(mesh):
        found, vptr = fn(state, probes)
    found = np.asarray(found)
    assert found[:64].all()
    miss_mask = ~np.isin(np.asarray(neg), keys)
    assert not found[64:][miss_mask].any()
    np.testing.assert_array_equal(np.asarray(vptr)[:64],
                                  np.searchsorted(keys, pos))


def test_empty_shard_masked_from_sentinel_probe():
    """A shard with no records keeps lo = hi = KEY_SENTINEL; a probe equal
    to the sentinel must not match it (it would index a zeroed model)."""
    keys = np.array([10, 20, 30, 40, 50], dtype=np.int64)  # 4 shards -> last empty
    vptrs = np.arange(5, dtype=np.int64)
    cfg = DistStoreConfig(n_keys=5, probe_batch=4)
    state = build_dist_state(keys, vptrs, n_shards=4, cfg=cfg)
    assert state["n"][3] == 0
    probes = jnp.asarray(np.array([KEY_SENTINEL, 10, KEY_SENTINEL - 1, 50],
                                  dtype=np.int64))
    hits = np.zeros(4, np.int32)
    for s in range(4):
        shard = {k: jnp.asarray(v[s: s + 1]) for k, v in state.items()}
        h, _ = dist_get_local(shard, probes, cfg.delta)
        hits += np.asarray(h, np.int32)
    np.testing.assert_array_equal(hits, [0, 1, 0, 1])


def test_build_dist_state_from_shards_variable_sizes():
    """The durable-plane builder sizes geometry to the live maxima, so
    shards recovered from directories of very different sizes stack."""
    rng = np.random.default_rng(9)
    k0 = np.sort(rng.choice(1 << 40, 5000, replace=False)).astype(np.int64)
    k1 = np.sort(rng.choice(1 << 40, 37, replace=False) + (1 << 41)).astype(np.int64)
    snaps = [(k0, np.arange(5000, dtype=np.int64)),
             (np.empty(0, np.int64), np.empty(0, np.int64)),
             (k1, np.arange(37, dtype=np.int64))]
    state = build_dist_state_from_shards(snaps, delta=8)
    assert state["keys"].shape[0] == 3
    np.testing.assert_array_equal(state["n"], [5000, 0, 37])
    probes = jnp.asarray(np.concatenate([k0[:64], k1, k0[:10] + 1]))
    hits = np.zeros(probes.shape[0], np.int32)
    vals = np.zeros(probes.shape[0], np.int64)
    for s in range(3):
        shard = {k: jnp.asarray(v[s: s + 1]) for k, v in state.items()}
        h, v = dist_get_local(shard, probes, 8)
        hits += np.asarray(h, np.int32)
        vals += np.where(np.asarray(h), np.asarray(v), 0)
    assert (hits[:101] == 1).all() and (hits[101:] == 0).all()
    np.testing.assert_array_equal(vals[:64], np.arange(64))
    np.testing.assert_array_equal(vals[64:101], np.arange(37))


# ------------------------------------------------------------- ShardedStore

def _shard_store_cfg(**kw):
    defaults = dict(granularity="level", policy="always", value_size=16,
                    lsm=LSMConfig(memtable_cap=1 << 10, file_cap=1 << 11,
                                  l1_cap_records=1 << 13),
                    engine=EngineConfig(seg_cap=4096))
    defaults.update(kw)
    return StoreConfig(**defaults)


def _values_for(keys: np.ndarray, version: int, value_size: int = 16):
    v = np.zeros((keys.shape[0], value_size), np.uint8)
    v[:, 0] = (keys % 251).astype(np.uint8)
    v[:, 1] = version % 251
    return v


def _sharded(tmp_path, keys, n_shards):
    bounds = tuple(int(b) for b in
                   np.quantile(keys, np.arange(1, n_shards) / n_shards))
    scfg = ShardedConfig(n_shards=n_shards, boundaries=bounds)
    return ShardedStore.open(str(tmp_path / "db"), scfg, _shard_store_cfg())


def test_sharded_store_roundtrip_values_and_tombstones(tmp_path):
    rng = np.random.default_rng(0)
    keys = rng.permutation(np.arange(1, 12001, dtype=np.int64) * 7)
    st = _sharded(tmp_path, keys, n_shards=2)
    for off in range(0, keys.shape[0], 2048):
        ks = keys[off: off + 2048]
        st.put_batch(ks, _values_for(ks, 0))
    # overwrites route to the same shard; tombstones shadow
    st.put_batch(keys[:2000], _values_for(keys[:2000], 1))
    st.delete_batch(keys[2000:3000])
    probes = np.concatenate([keys, keys[:500] + 1])
    found, vals = st.get_batch(probes, with_values=True)
    assert found[:2000].all() and (vals[:2000, 1] == 1).all()
    assert not found[2000:3000].any()
    assert found[3000:12000].all() and (vals[3000:12000, 1] == 0).all()
    miss = ~np.isin(keys[:500] + 1, keys)
    assert not found[12000:][miss].any()
    st.close()


def test_sharded_store_kill_reopen_from_directories(tmp_path):
    """The acceptance scenario: killed after N batched puts, the store
    reopens from its per-shard directories alone and answers a mixed
    hit/miss GET through the shard_map path, with persisted file- and
    level-models serving lookups before any learning job runs."""
    rng = np.random.default_rng(1)
    keys = rng.permutation(np.arange(1, 20001, dtype=np.int64) * 3)
    n_shards = 4 if len(jax.devices()) >= 4 else 2
    st = _sharded(tmp_path, keys, n_shards)
    flushed, tail = keys[:16384], keys[16384:17000]
    for off in range(0, flushed.shape[0], 4096):
        ks = flushed[off: off + 4096]
        st.put_batch(ks, _values_for(ks, 0))
    st.flush_all()
    st.learn_all()                     # file + level models, all persisted
    st.put_batch(tail, _values_for(tail, 0))   # WAL-only at kill time
    del st  # CRASH: no close
    import gc
    gc.collect()

    st2 = ShardedStore.open(str(tmp_path / "db"))   # directories alone
    s = st2.stats()
    assert s["n_shards"] == n_shards
    assert s["files_learned"] == 0                  # nothing relearned
    assert s["models_recovered"] > 0
    assert s["level_models_recovered"] > 0
    assert all(not sh.executor.queue and not sh.executor.running
               for sh in st2.shards)
    # mixed GET: flushed keys (snapshot path), WAL-recovered keys
    # (memtable overlay), and misses
    probes = np.concatenate([flushed[:4000], tail, flushed[:500] + 1])
    found, vals = st2.get_batch(probes, with_values=True)
    n_hit = 4000 + tail.shape[0]
    assert found[:n_hit].all()
    assert (vals[:n_hit, 0] == (probes[:n_hit] % 251)).all()
    miss = ~np.isin(flushed[:500] + 1, keys[:17000])
    assert not found[n_hit:][miss].any()
    # the GET ran with zero learning jobs: persisted models served it
    assert all(sh.executor.jobs_done == 0 for sh in st2.shards)
    # per-shard engine path is model-pure too (no baseline lookups)
    f, _ = st2.shards[0].get_batch(flushed[:512])
    assert st2.shards[0].lookups_baseline_path == 0
    st2.close()

    # topology guards: wrong shard count / boundaries refused, and a lost
    # SHARDS.json over live shard directories must never re-create one
    with pytest.raises(ValueError, match="shards"):
        ShardedStore.open(str(tmp_path / "db"),
                          ShardedConfig(n_shards=n_shards + 1))
    with pytest.raises(ValueError, match="boundaries"):
        ShardedStore.open(str(tmp_path / "db"),
                          ShardedConfig(n_shards=n_shards,
                                        boundaries=tuple(
                                            range(1, n_shards))))
    os.unlink(str(tmp_path / "db" / "SHARDS.json"))
    with pytest.raises(RuntimeError, match="SHARDS.json"):
        ShardedStore.open(str(tmp_path / "db"))


def test_sharded_config_rejects_duplicate_boundaries():
    with pytest.raises(ValueError, match="ascending"):
        ShardedConfig(n_shards=3, boundaries=(100, 100)).splits()
    with pytest.raises(ValueError, match="ascending"):
        ShardedConfig(n_shards=3, boundaries=(200, 100)).splits()
    with pytest.raises(ValueError, match="ascending"):
        ShardedConfig(n_shards=4, boundaries=(100, 200)).splits()


def test_sharded_state_epoch_refreshes_on_memtable_roll(tmp_path):
    rng = np.random.default_rng(2)
    keys = rng.permutation(np.arange(1, 6001, dtype=np.int64) * 11)
    st = _sharded(tmp_path, keys, n_shards=2)
    small = keys[:512]
    st.put_batch(small, _values_for(small, 0))
    f, _ = st.get_batch(small)         # served by the memtable overlay
    assert f.all()
    e0 = st.state_epoch
    for off in range(0, keys.shape[0], 2048):   # enough to roll memtables
        ks = keys[off: off + 2048]
        st.put_batch(ks, _values_for(ks, 1))
    st.flush_all()
    f, _ = st.get_batch(keys)          # now served by the snapshot path
    assert f.all()
    assert st.state_epoch > e0         # device state refreshed on the roll
    # a pure read does not rebuild the state
    e1 = st.state_epoch
    st.get_batch(keys[:256])
    assert st.state_epoch == e1
    st.close()


def test_load_shard_snapshot_matches_live_tree(tmp_path):
    rng = np.random.default_rng(3)
    keys = rng.permutation(np.arange(1, 8001, dtype=np.int64) * 5)
    st = _sharded(tmp_path, keys, n_shards=2)
    st.put_batch(keys, _values_for(keys, 0))
    st.delete_batch(keys[:1000])
    st.flush_all()
    from repro.distributed import merge_live
    want = [merge_live(list(sh.tree.all_files())) for sh in st.shards]
    st.close()
    for i, (wk, wv) in enumerate(want):
        gk, gv = load_shard_snapshot(str(tmp_path / "db" / f"shard-{i}"))
        np.testing.assert_array_equal(gk, wk)
        np.testing.assert_array_equal(gv, wv)
        assert not np.isin(keys[:1000], gk).any()


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >=4 devices for a 4-shard mesh")
def test_sharded_store_uses_shard_map_on_multidevice(tmp_path):
    rng = np.random.default_rng(4)
    keys = rng.permutation(np.arange(1, 16001, dtype=np.int64) * 13)
    st = _sharded(tmp_path, keys, n_shards=4)
    assert st.uses_shard_map
    st.put_batch(keys, _values_for(keys, 0))
    st.flush_all()
    probes = np.concatenate([keys[:4096], keys[:1024] + 1])
    found, _ = st.get_batch(probes)
    assert found[:4096].all()
    miss = ~np.isin(keys[:1024] + 1, keys)
    assert not found[4096:][miss].any()
    st.close()
