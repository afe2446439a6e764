"""Range-partitioned Bourbon store across the mesh (DESIGN.md §4).

The cluster analogue of the paper's read path: the sorted key space is
range-partitioned over every mesh device (the cluster-level "FindFiles"),
each shard holds its slice plus a local PLR model, and a batched GET is one
shard_map program:

    all-gather the probe batch (tiny: 8B/probe)
      -> each shard answers probes in its own range via the learned path
         (segment compare-count + FMA + delta-window probe)
      -> masked psum combines results (each probe owned by exactly one shard)

Collective bytes per GET: B*8 all-gather + 2*B*8 all-reduce — independent of
DB size; this is what the bourbon_kv dry-run cells measure.  The state is
built once from a sorted snapshot (an immutable "level" in paper terms) and
never mutated in place — updates land in per-host memtables and roll into a
new snapshot (BourbonStore semantics), so the distributed plane needs no
write locks.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from .plr import greedy_plr_np

__all__ = ["DistStoreConfig", "build_dist_state", "build_dist_state_from_shards",
           "dist_state_specs", "build_dist_get", "dist_get_local", "next_pow2"]

KEY_SENTINEL = np.iinfo(np.int64).max


def next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class DistStoreConfig:
    n_keys: int              # global keys in the snapshot
    probe_batch: int         # global probes per GET step
    delta: int = 8
    seg_cap: int = 512       # per-shard PLR segments (padded)

    def shard_cap(self, n_shards: int) -> int:
        return next_pow2(-(-self.n_keys // n_shards))


def _stack_shards(chunks, delta: int, cap: int | None,
                  seg_cap: int | None, models=None, filters=None):
    """Stack per-shard sorted (keys, vptrs) snapshots into the device-state
    dict, fitting one PLR model per shard.  ``cap``/``seg_cap`` default to
    the live maxima (padded to a power of two) so disk-recovered shards of
    any size fit; passing them pins the legacy fixed geometry.  ``models``
    supplies pre-fit per-shard PLR models (must use the same ``delta``) so
    a caller refreshing one shard need not refit the rest.  ``filters``
    (per-shard LevelFilter or None) adds stacked bloom rows ``fbits``
    (S, W) / ``fnw`` (S,) to the state so the GET kernel can prune shards
    that definitely lack a probe; ``fnw == 0`` marks no-filter rows."""
    n_shards = len(chunks)
    if models is None:
        models = [greedy_plr_np(k, delta=delta) if k.shape[0] else None
                  for k, _ in chunks]
    if cap is None:
        cap = max(64, next_pow2(max((k.shape[0] for k, _ in chunks),
                                    default=1)))
    if seg_cap is None:
        seg_cap = max(16, next_pow2(max(
            (int(m.n_segments) for m in models if m is not None), default=1)))
    ks = np.full((n_shards, cap), KEY_SENTINEL, np.int64)
    vs = np.full((n_shards, cap), -1, np.int64)
    ns = np.zeros((n_shards,), np.int32)
    lo = np.full((n_shards,), KEY_SENTINEL, np.int64)
    hi = np.full((n_shards,), KEY_SENTINEL, np.int64)
    starts = np.full((n_shards, seg_cap), np.inf, np.float64)
    slopes = np.zeros((n_shards, seg_cap), np.float64)
    icepts = np.zeros((n_shards, seg_cap), np.float64)
    nseg = np.zeros((n_shards,), np.int32)
    for s, ((chunk, vp), m) in enumerate(zip(chunks, models)):
        if chunk.shape[0] == 0:
            continue
        if chunk.shape[0] > cap:
            raise ValueError(f"shard {s} holds {chunk.shape[0]} keys > "
                             f"cap {cap}")
        ks[s, : chunk.shape[0]] = chunk
        vs[s, : chunk.shape[0]] = vp
        ns[s] = chunk.shape[0]
        lo[s], hi[s] = chunk[0], chunk[-1]
        k = int(m.n_segments)
        if k > seg_cap:
            raise ValueError(f"shard {s} model needs {k} segments > "
                             f"seg_cap {seg_cap}")
        starts[s, :k] = np.asarray(m.starts)[:k]
        slopes[s, :k] = np.asarray(m.slopes)[:k]
        icepts[s, :k] = np.asarray(m.intercepts)[:k]
        nseg[s] = k
    out = {"keys": ks, "vptrs": vs, "n": ns, "lo": lo, "hi": hi,
           "starts": starts, "slopes": slopes, "icepts": icepts,
           "nseg": nseg}
    if filters is not None:
        fw = max(64, next_pow2(max(
            (f.n_words for f in filters if f is not None), default=1)))
        fbits = np.zeros((n_shards, fw), np.uint64)
        fnw = np.zeros((n_shards,), np.int32)
        for s, f in enumerate(filters):
            if f is not None:
                fbits[s, : f.n_words] = f.bits
                fnw[s] = f.n_words
        out["fbits"] = fbits
        out["fnw"] = fnw
    return out


def build_dist_state(keys: np.ndarray, vptrs: np.ndarray, n_shards: int,
                     cfg: DistStoreConfig):
    """Host build: one globally sorted snapshot -> equal-count range chunks
    stacked into (n_shards, C) arrays + per-shard PLR models."""
    n = keys.shape[0]
    per = -(-n // n_shards)
    chunks = [(keys[s * per: (s + 1) * per], vptrs[s * per: (s + 1) * per])
              for s in range(n_shards)]
    return _stack_shards(chunks, cfg.delta, cfg.shard_cap(n_shards),
                         cfg.seg_cap)


def build_dist_state_from_shards(snapshots, delta: int = 8, models=None,
                                 filters=None):
    """Device state from per-shard snapshots (the durable-plane entry
    point): ``snapshots`` is a list of (keys, vptrs) pairs, one per range
    partition, each sorted by key with shadowed versions and tombstones
    already dropped — exactly what ``repro.distributed`` derives from a
    shard directory's sstables.  Geometry (row capacity, segment cap) is
    sized to the live maxima, so shards recovered from disk never need a
    global key count up front.  ``models`` optionally carries pre-fit
    per-shard PLR models (same ``delta``), letting an epoch-cached caller
    refit only the shards whose snapshot actually changed.  ``filters``
    optionally carries per-shard bloom filters (see ``_stack_shards``)."""
    return _stack_shards([(np.asarray(k, np.int64), np.asarray(v, np.int64))
                          for k, v in snapshots], delta, None, None, models,
                         filters)


def dist_state_specs(mesh, cfg: DistStoreConfig):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    n_shards = mesh.size
    cap = cfg.shard_cap(n_shards)
    ax = tuple(mesh.axis_names)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            (n_shards,) + shape, dtype,
            sharding=NamedSharding(mesh, P(ax)))

    return {
        "keys": sds((cap,), jnp.int64), "vptrs": sds((cap,), jnp.int64),
        "n": sds((), jnp.int32), "lo": sds((), jnp.int64),
        "hi": sds((), jnp.int64),
        "starts": sds((cfg.seg_cap,), jnp.float64),
        "slopes": sds((cfg.seg_cap,), jnp.float64),
        "icepts": sds((cfg.seg_cap,), jnp.float64),
        "nseg": sds((), jnp.int32),
    }


def dist_get_local(shard, probes, delta: int, seg_search: str = "bisect",
                   maybe=None, k_hashes: int = 7):
    """One shard's answers for the full probe batch (masked outside its
    range).  shard leaves have a leading length-1 shard dim inside shard_map.

    seg_search: "bisect" (log2(S) gather steps; bytes ~ B*8 per step) or
    "compare" (one (B, S) broadcast compare; bytes ~ B*S*8 — memory-bound at
    large B; kept for the perf log).

    Filter pruning: ``maybe`` (a (B,) bool mask the caller probed
    separately) or, absent that, the shard's own ``fbits``/``fnw`` bloom
    row probed in-kernel; probes the filter rules out skip the descent."""
    import math
    keys = shard["keys"][0]
    C = keys.shape[0]
    # an empty shard keeps lo = hi = KEY_SENTINEL, so a probe equal to the
    # sentinel would otherwise "match" and index the zeroed model — mask
    # empty shards out explicitly
    mine = ((shard["n"][0] > 0)
            & (probes >= shard["lo"][0]) & (probes <= shard["hi"][0]))
    if maybe is None and "fbits" in shard:
        from repro.kernels.ref import bloom_probe_stack_ref
        maybe = bloom_probe_stack_ref(shard["fbits"], shard["fnw"],
                                      probes, k_hashes)[0]
    if maybe is not None:
        mine = mine & maybe
    pf = probes.astype(jnp.float64)
    starts = shard["starts"][0]
    if seg_search == "compare":
        seg = jnp.maximum(
            jnp.sum(starts[None, :] <= pf[:, None], axis=-1) - 1, 0)
    else:
        S = starts.shape[0]
        steps = max(1, math.ceil(math.log2(S + 1)))
        lo_i = jnp.zeros(pf.shape, jnp.int32)
        hi_i = jnp.broadcast_to(jnp.maximum(shard["nseg"][0], 1),
                                pf.shape).astype(jnp.int32)

        def body(_, lohi):
            lo, hi = lohi
            active = lo < hi
            mid = (lo + hi) >> 1
            kv = starts[jnp.clip(mid, 0, S - 1)]
            right = kv <= pf
            lo2 = jnp.where(right, mid + 1, lo)
            hi2 = jnp.where(right, hi, mid)
            return jnp.where(active, lo2, lo), jnp.where(active, hi2, hi)

        lo_i, _ = jax.lax.fori_loop(0, steps, body, (lo_i, hi_i))
        seg = jnp.maximum(lo_i - 1, 0)
    pos = shard["slopes"][0][seg] * pf + shard["icepts"][0][seg]
    pos = jnp.clip(jnp.round(pos).astype(jnp.int32), 0,
                   jnp.maximum(shard["n"][0] - 1, 0))
    offs = jnp.arange(-(delta + 1), delta + 2, dtype=jnp.int32)
    win_idx = jnp.clip(pos[:, None] + offs[None, :], 0, C - 1)
    win = keys[win_idx]
    eq = win == probes[:, None]
    hit = jnp.any(eq, axis=-1) & mine
    rel = jnp.argmax(eq, axis=-1)
    idx = win_idx[jnp.arange(probes.shape[0]), rel]
    vptr = jnp.where(hit, shard["vptrs"][0][idx], 0)
    return hit, vptr


def build_dist_get(mesh, cfg: DistStoreConfig, seg_search: str = "bisect",
                   combine: str = "reduce_scatter",
                   state_keys: tuple | None = None, k_hashes: int = 7):
    """Returns jit(dist_get)(state, probes) -> (found, vptr).

    combine="reduce_scatter": results return only to each probe's origin
    shard (psum_scatter; half the payload of an all-reduce, outputs stay
    sharded).  combine="allreduce": every device gets every result (v1,
    kept for the perf log).  found rides as int8 (each probe has exactly
    one owner, so the reduced value is 0/1 — no overflow).

    ``state_keys`` pins the state-dict layout (pass the caller's actual
    ``tuple(state)`` when it carries the optional ``fbits``/``fnw`` filter
    rows); the default is the filterless nine-leaf legacy layout."""
    ax = tuple(mesh.axis_names)
    state_spec = P(ax)
    probe_spec = P(ax)   # probes arrive sharded by origin device
    if state_keys is None:
        state_keys = ("keys", "vptrs", "n", "lo", "hi", "starts", "slopes",
                      "icepts", "nseg")

    def body(shard, probes_local):
        probes = probes_local
        for a in ax:
            probes = jax.lax.all_gather(probes, a, tiled=True)
        hit, vptr = dist_get_local(shard, probes, cfg.delta, seg_search,
                                   k_hashes=k_hashes)
        found = hit.astype(jnp.int8)
        vsum = jnp.where(hit, vptr, 0)
        if combine == "reduce_scatter":
            # the TPU compiler has no 64-bit reduce-scatter: combine the
            # vptrs as (B, 2) uint32 halves.  Each probe has exactly one
            # owner and every other shard contributes zeros, so each half
            # sums with no carry and the bitcast back is exact
            halves = jax.lax.bitcast_convert_type(vsum, jnp.uint32)
            for a in reversed(ax):
                found = jax.lax.psum_scatter(found, a, tiled=True)
                halves = jax.lax.psum_scatter(halves, a, tiled=True)
            vsum = jax.lax.bitcast_convert_type(halves, jnp.int64)
        else:
            for a in ax:
                found = jax.lax.psum(found, a)
                vsum = jax.lax.psum(vsum, a)
        return found > 0, jnp.where(found > 0, vsum, -1)

    out_spec = probe_spec if combine == "reduce_scatter" else P()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=({k: state_spec for k in state_keys}, probe_spec),
        out_specs=(out_spec, out_spec),
        check_vma=False)
    return jax.jit(fn)
