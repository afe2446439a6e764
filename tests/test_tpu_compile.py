"""The device programs `chip_smoke.py` runs compile for a TPU v5e.

Each test compiles one program for a described (not attached) ``v5e:2x2``
topology at the widths of the smoke's default run, so a program the chip's
compiler would refuse fails here, with no chip.  Nothing runs.

The topology is described only inside the ``topo`` fixture: a module that
loads the TPU library at import would make each test worker collect
different tests.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (AxisType, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.distributed import DistStoreConfig, build_dist_get
from repro.core.engine import (DeviceLevel, DeviceState, EngineConfig,
                               LevelModel, LookupEngine)
from repro.core.lsm import N_LEVELS
from repro.distributed.sharded import (_local_get_all_shards,
                                       _shard_filter_probe)

N_SHARDS = 4
DELTA = 8
BLOOM_K = 7
# the server pads a GET batch to at most max_batch_keys (1024) probes
SERVED_PROBES = 1024
# served store, one chip: 16M keys over 4 shards -> 4M-key rows, PLR
# segment and bloom-word rows as the smoke's state build reports them
ONE_CHIP_ROW = (1 << 22, 1 << 15, 1 << 20)       # (keys, segments, words)
# served store, four chips: the 4M-key default of `--chips 4`
FOUR_CHIP_ROW = (1 << 20, 1 << 13, 1 << 18)
# paper path at 4M keys: per-level (files, records, blocks, bloom words,
# segments), the LookupEngine geometry of the smoke's loaded tree, and the
# 4096-key half-absent batch padded to 3072 after the host filter screen
ENGINE_LEVELS = ((2, 4096, 16, 512, 128), (8, 32768, 128, 8192, 256),
                 (64, 32768, 128, 8192, 256), (128, 32768, 128, 8192, 256),
                 (2, 256, 1, 1, 16), (2, 256, 1, 1, 16), (2, 256, 1, 1, 16))
ENGINE_LIVE = (True,) * 4 + (False,) * 3
ENGINE_FILTER_WORDS = 1 << 18
ENGINE_PROBES = 3072


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shard_state(row, sharding) -> dict:
    """Shapes of ShardedStore.device_state() with filters on."""
    cap, seg, words = row
    s = partial(_sds, sharding=sharding)
    return {"keys": s((N_SHARDS, cap), jnp.int64),
            "vptrs": s((N_SHARDS, cap), jnp.int64),
            "n": s((N_SHARDS,), jnp.int32),
            "lo": s((N_SHARDS,), jnp.int64),
            "hi": s((N_SHARDS,), jnp.int64),
            "starts": s((N_SHARDS, seg), jnp.float64),
            "slopes": s((N_SHARDS, seg), jnp.float64),
            "icepts": s((N_SHARDS, seg), jnp.float64),
            "nseg": s((N_SHARDS,), jnp.int32),
            "fbits": s((N_SHARDS, words), jnp.uint64),
            "fnw": s((N_SHARDS,), jnp.int32)}


def test_fused_get_compiles_for_one_chip(one_chip):
    state = _shard_state(ONE_CHIP_ROW, one_chip)
    probes = _sds((SERVED_PROBES,), jnp.int64, one_chip)
    maybe = _sds((N_SHARDS, SERVED_PROBES), jnp.bool_, one_chip)
    compiled = _local_get_all_shards.lower(state, probes, N_SHARDS, DELTA,
                                           maybe).compile()
    # the key and vptr rows stay resident; the filter rows are not read
    # when the caller passes the maybe-mask
    rows = 2 * 8 * N_SHARDS * ONE_CHIP_ROW[0]
    assert compiled.memory_analysis().argument_size_in_bytes >= rows


def test_filter_probe_compiles_for_one_chip(one_chip):
    state = _shard_state(ONE_CHIP_ROW, one_chip)
    probes = _sds((SERVED_PROBES,), jnp.int64, one_chip)
    compiled = _shard_filter_probe.lower(state["fbits"], state["fnw"],
                                         probes, BLOOM_K).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= (
        N_SHARDS * SERVED_PROBES)


def test_lookup_engine_compiles_for_one_chip(one_chip):
    s = partial(_sds, sharding=one_chip)
    levels, models = [], []
    for f, c, nb, w, seg in ENGINE_LEVELS:
        levels.append(DeviceLevel(
            s((f, c), jnp.int64), s((f, c), jnp.int64), s((f,), jnp.int32),
            s((f, nb), jnp.int64), s((f,), jnp.int32), s((f, w), jnp.uint64),
            s((f,), jnp.int32), s((f,), jnp.int64), s((f,), jnp.int64),
            s((f, seg), jnp.float64), s((f, seg), jnp.float64),
            s((f, seg), jnp.float64), s((f,), jnp.int32),
            s((), jnp.int32)))
        lseg = EngineConfig().level_seg_cap
        models.append(LevelModel(
            s((lseg,), jnp.float64), s((lseg,), jnp.float64),
            s((lseg,), jnp.float64), s((), jnp.int32), s((f,), jnp.int64)))
    state = DeviceState(tuple(levels), tuple(models))
    engine = LookupEngine(EngineConfig())
    # the program LookupEngine._jitted_lookup builds for the smoke's batch
    fn = partial(engine._lookup_impl, mode="model_pure",
                 l0_slots=(ENGINE_LEVELS[0][0],), live_levels=ENGINE_LIVE)
    lookup = jax.jit(lambda st, p, fm, fh: fn(st, p, fmaybe=fm, fhas=fh,
                                              use_filters=True))
    lookup.lower(state, s((ENGINE_PROBES,), jnp.int64),
                 s((N_LEVELS, ENGINE_PROBES), jnp.bool_),
                 s((N_LEVELS,), jnp.bool_)).compile()
    assert engine.trace_count == 1


def test_shard_map_get_compiles_for_four_chips(topo):
    mesh = jax.make_mesh((N_SHARDS,), ("shard",),
                         axis_types=(AxisType.Explicit,),
                         devices=topo.devices[:N_SHARDS])
    by_shard = NamedSharding(mesh, P("shard"))
    state = _shard_state(FOUR_CHIP_ROW, by_shard)
    probes = _sds((SERVED_PROBES,), jnp.int64, by_shard)
    fn = build_dist_get(mesh, DistStoreConfig(n_keys=0, probe_batch=0,
                                              delta=DELTA),
                        state_keys=tuple(sorted(state)), k_hashes=BLOOM_K)
    with jax.set_mesh(mesh):
        compiled = fn.lower(state, probes).compile()
    hlo = compiled.as_text()
    # probes gathered to every chip, answers combined back (the compiler
    # may lower the reduce-scatter as an all-reduce)
    assert "all-gather" in hlo
    assert "reduce-scatter" in hlo or "all-reduce" in hlo
    # each chip holds one shard row, not the whole stacked state
    cap = FOUR_CHIP_ROW[0]
    assert compiled.memory_analysis().argument_size_in_bytes < (
        N_SHARDS * cap * 16)
