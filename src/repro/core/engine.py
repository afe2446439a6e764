"""Batched lookup data plane (the TPU-native reformulation of Bourbon's
read path).

The host LSM (lsm.py) is stacked into padded per-level device arrays; a
lookup batch of B probe keys is then one tensor program implementing the
paper's steps (Fig. 1 / Fig. 6):

  baseline path:  FindFiles -> SearchIB (fence binsearch) -> SearchFB (bloom)
                  -> SearchDB (in-block binsearch) -> ReadValue
  model path:     FindFiles -> ModelLookup (PLR segment binsearch + FMA)
                  -> SearchFB -> LoadChunk+LocateKey (delta-window probe)
                  -> ReadValue

All steps are branch-free vectorized gathers (pure jnp here; the Pallas
kernels in repro.kernels implement the same contracts for TPU).  Per-level
positive/negative internal-lookup *counts* are computed in-graph and returned
as tiny vectors for the cost-benefit analyzer.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .bloom import bloom_hashes, bloom_word_bit
from .lsm import LSMTree, N_LEVELS
from .sstable import BLOCK_RECORDS

__all__ = ["EngineConfig", "DeviceLevel", "DeviceState", "FilterState",
           "LookupEngine", "LookupResult", "PendingLookup", "binsearch_rows"]

KEY_SENTINEL = np.iinfo(np.int64).max


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


# ----------------------------------------------------------------------------
# pytrees
# ----------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceLevel:
    keys: jnp.ndarray        # (F, C) int64, padded KEY_SENTINEL
    vptrs: jnp.ndarray       # (F, C) int64
    n: jnp.ndarray           # (F,) int32 live records per file
    fences: jnp.ndarray      # (F, NB) int64 padded KEY_SENTINEL
    n_blocks: jnp.ndarray    # (F,) int32
    bloom: jnp.ndarray       # (F, W) uint64
    bloom_nw: jnp.ndarray    # (F,) int32 live filter words (hash modulus)
    min_key: jnp.ndarray     # (F,) int64 (SENTINEL when slot empty)
    max_key: jnp.ndarray     # (F,) int64 (SENTINEL when slot empty)
    starts: jnp.ndarray      # (F, S) f64 PLR segment starts (+inf pad)
    slopes: jnp.ndarray      # (F, S) f64
    icepts: jnp.ndarray      # (F, S) f64
    nseg: jnp.ndarray        # (F,) int32 (0 = no model)
    n_files: jnp.ndarray     # () int32

    def tree_flatten(self):
        # NOT dataclasses.astuple: astuple deep-copies every leaf, and
        # flatten runs on every jitted dispatch — the copy dominated the
        # host-side cost of small-batch lookups
        return (self.keys, self.vptrs, self.n, self.fences, self.n_blocks,
                self.bloom, self.bloom_nw, self.min_key, self.max_key,
                self.starts, self.slopes, self.icepts, self.nseg,
                self.n_files), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LevelModel:
    """Level-granularity PLR (§4.3): key -> global index in the level."""
    starts: jnp.ndarray      # (S,) f64
    slopes: jnp.ndarray      # (S,) f64
    icepts: jnp.ndarray      # (S,) f64
    nseg: jnp.ndarray        # () int32 (0 = no model)
    file_start: jnp.ndarray  # (F,) int64 global index of each file's first key

    def tree_flatten(self):
        return (self.starts, self.slopes, self.icepts, self.nseg,
                self.file_start), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceState:
    levels: tuple            # N_LEVELS DeviceLevel
    level_models: tuple      # N_LEVELS (LevelModel | None -> encoded w/ nseg=0)

    def tree_flatten(self):
        return (self.levels, self.level_models), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FilterState:
    """The filter plane: per-level bloom filters stacked to a padded (L, W)
    device array, probed by one batched kernel call ahead of the descent."""
    bits: jnp.ndarray        # (N_LEVELS, W) uint64, width-padded
    nw: jnp.ndarray          # (N_LEVELS,) int32 build-time words; 0 = none
    has: jnp.ndarray         # (N_LEVELS,) bool — nw > 0, precomputed

    def tree_flatten(self):
        return (self.bits, self.nw, self.has), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class LookupResult:
    """Materialized lookup answers.

    ``found`` / ``vptr`` / ``served_level`` are host arrays (the caller
    asked for them by resolving).  The per-level CBA counter vectors stay
    on device until first touched: callers that only want values (the
    serving hot path) never pay the extra device->host transfer, while
    the stats path (`BourbonStore._account_lookup`) materializes them
    once, lazily, on access.

    ``n_materializations`` is a class-wide count of device->host counter
    transfers — the observability regression tests assert that attaching
    the metrics plane adds zero of these per batch."""

    n_materializations = 0

    def __init__(self, found, vptr, served_level, pos_counts, neg_counts,
                 values=None):
        self.found = found                 # (B,) bool
        self.vptr = vptr                   # (B,) int64
        self.served_level = served_level   # (B,) int8, -1 = miss everywhere
        self._pos_dev = pos_counts         # per level (F,) device int32
        self._neg_dev = neg_counts
        self._pos_np: list | None = None
        self._neg_np: list | None = None
        self.values = values

    @property
    def pos_counts(self) -> list:
        if self._pos_np is None:
            LookupResult.n_materializations += 1
            self._pos_np = [np.asarray(p) for p in self._pos_dev]
        return self._pos_np

    @property
    def neg_counts(self) -> list:
        if self._neg_np is None:
            LookupResult.n_materializations += 1
            self._neg_np = [np.asarray(n) for n in self._neg_dev]
        return self._neg_np


@dataclasses.dataclass
class PendingLookup:
    """The dispatch half of a lookup: every field is a device array still
    being computed (JAX async dispatch).  Nothing here blocks the host —
    `resolve()` is the synchronization point, so a caller can dispatch
    batch N+1 (admission, cache probing, memtable overlay) while the
    device works on batch N."""
    found: jnp.ndarray       # (B,) bool, device
    vptr: jnp.ndarray        # (B,) int64, device
    served: jnp.ndarray      # (B,) int8, device
    pos_counts: tuple        # per level (F,) int32, device
    neg_counts: tuple
    values: jnp.ndarray | None = None

    def resolve(self) -> LookupResult:
        """Block on the device results and hand back host arrays (counter
        vectors stay lazy — see LookupResult)."""
        return LookupResult(np.asarray(self.found), np.asarray(self.vptr),
                            np.asarray(self.served),
                            self.pos_counts, self.neg_counts,
                            None if self.values is None
                            else np.asarray(self.values))


# ----------------------------------------------------------------------------
# vectorized primitives
# ----------------------------------------------------------------------------

def binsearch_rows(mat: jnp.ndarray, rows: jnp.ndarray, probes: jnp.ndarray,
                   lo: jnp.ndarray, hi: jnp.ndarray, side: str = "left") -> jnp.ndarray:
    """Batched bisect over rows of a (F, C) matrix.

    Returns per-probe insertion index within [lo, hi).  log2(C) gather steps —
    the jnp oracle for kernels/sstable_search.
    """
    C = mat.shape[-1]
    steps = max(1, math.ceil(math.log2(C + 1)))
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        kv = mat[rows, jnp.clip(mid, 0, C - 1)]
        go_right = (kv < probes) if side == "left" else (kv <= probes)
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(go_right, hi, mid)
        return jnp.where(active, lo2, lo), jnp.where(active, hi2, hi)

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def count_le_rows(mat: jnp.ndarray, rows: jnp.ndarray, probes: jnp.ndarray,
                  side: str = "right") -> jnp.ndarray:
    """Broadcast compare-count over gathered rows: #entries {<, <=} probe.
    One (B, W) gather + one vectorized compare + one reduce — the VPU-native
    replacement for a serial bisect when W is small (fences, PLR segments,
    data blocks)."""
    rowvals = mat[rows]                      # (B, W)
    p = probes[:, None].astype(rowvals.dtype)
    cmp = (rowvals <= p) if side == "right" else (rowvals < p)
    return jnp.sum(cmp, axis=-1).astype(jnp.int32)


def bloom_probe_rows(bits: jnp.ndarray, nwords: jnp.ndarray, rows: jnp.ndarray,
                     probes: jnp.ndarray, k_hashes: int) -> jnp.ndarray:
    """Row-indexed bloom probe: bits (F, W), nwords (F,), rows (B,).

    Gathers only the k addressed words per probe (never whole filter rows —
    that would move B*W bytes per call)."""
    widx, b = bloom_word_bit(bloom_hashes(probes, k_hashes),
                             nwords[rows])                    # (k, B)
    word = bits[rows[None, :], jnp.clip(widx, 0, bits.shape[-1] - 1)]
    return jnp.all((word >> b) & jnp.uint64(1) == jnp.uint64(1), axis=0)


# ----------------------------------------------------------------------------
# engine
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class EngineConfig:
    plr_delta: int = 8
    bloom_k: int = 7
    block_records: int = BLOCK_RECORDS
    seg_cap: int = 4096          # max PLR segments per file
    level_seg_cap: int = 65536   # max PLR segments per level model
    fetch_values: bool = False
    filter_impl: str = "ref"     # filter-plane probe kernel impl (ops._mode)


class LookupEngine:
    """Builds device state from the host tree and runs jitted lookups."""

    def __init__(self, cfg: EngineConfig) -> None:
        self.cfg = cfg
        self._state_cache: dict[int, DeviceLevel] = {}
        self._state_versions: list[int] = [-1] * N_LEVELS
        self._lm_versions: list = [-1] * N_LEVELS
        self._lm_cache: dict[int, LevelModel] = {}
        self._jit_cache: dict = {}
        # traces of _lookup_impl actually taken (incremented at trace
        # time): a fresh DeviceState with unchanged geometry must reuse
        # the cached program — regression-tested, since a silent retrace
        # per epoch would swamp the lookups it serves
        self.trace_count = 0
        # stamp for level models that arrive without an epoch: unique,
        # decreasing, never reused — store-fit models carry epochs >= 0
        self._unstamped_epoch = -2
        # per-level (model_probes, baseline_probes) attribution, computed
        # in-graph and accumulated as a single (N_LEVELS, 2) device add
        # per dispatched batch — never synced to the host until
        # probe_split_np() (the obs snapshot path) asks.  Off by default:
        # BourbonStore.attach_obs flips it on
        self.record_probe_split = False
        self.probe_split_acc = None
        self.probe_acc_materializations = 0   # host syncs of the acc
        # filter plane: stacked (L, W) device filters, cached by the
        # per-level filter epochs (same discipline as the lm cache); the
        # (L, 2) [pruned, false-positive] counters accumulate in-graph
        self._filter_cache: tuple | None = None
        self.filter_stats_acc = None
        self.filter_acc_materializations = 0  # host syncs of the filter acc

    # ---------------------------------------------------------------- build
    def _build_level(self, tables, cfg: EngineConfig) -> DeviceLevel:
        F = max(2, _next_pow2(len(tables) + 1))
        C = max(cfg.block_records,
                _next_pow2(max((t.n for t in tables), default=1)))
        NB = max(1, C // cfg.block_records)
        W = max(1, _next_pow2(max((t.bloom.shape[0] for t in tables), default=1)))
        # size the segment arrays to the live maximum: the bisect step count
        # is log2(S), so padding to cfg.seg_cap would burn gather steps
        live_ns = [int(t.model.n_segments) for t in tables
                   if t.model is not None]
        S = max(16, _next_pow2(max(live_ns, default=1)))
        keys = np.full((F, C), KEY_SENTINEL, np.int64)
        vptrs = np.full((F, C), -1, np.int64)
        n = np.zeros(F, np.int32)
        fences = np.full((F, NB), KEY_SENTINEL, np.int64)
        n_blocks = np.zeros(F, np.int32)
        bloom = np.zeros((F, W), np.uint64)
        bloom_nw = np.ones(F, np.int32)
        min_key = np.full(F, KEY_SENTINEL, np.int64)
        max_key = np.full(F, KEY_SENTINEL, np.int64)
        starts = np.full((F, S), np.inf, np.float64)
        slopes = np.zeros((F, S), np.float64)
        icepts = np.zeros((F, S), np.float64)
        nseg = np.zeros(F, np.int32)
        for i, t in enumerate(tables):
            keys[i, : t.n] = t.keys
            vptrs[i, : t.n] = t.vptrs
            n[i] = t.n
            fences[i, : t.fences.shape[0]] = t.fences
            n_blocks[i] = t.fences.shape[0]
            bloom[i, : t.bloom.shape[0]] = t.bloom
            bloom_nw[i] = t.bloom.shape[0]
            min_key[i] = t.min_key
            max_key[i] = t.max_key
            if t.model is not None:
                ns = int(t.model.n_segments)
                if ns > S:
                    raise ValueError(f"file model has {ns} segments > cap {S}")
                starts[i, :ns] = np.asarray(t.model.starts)[:ns]
                slopes[i, :ns] = np.asarray(t.model.slopes)[:ns]
                icepts[i, :ns] = np.asarray(t.model.intercepts)[:ns]
                nseg[i] = ns
        dev = jax.device_put
        return DeviceLevel(dev(keys), dev(vptrs), dev(n), dev(fences),
                           dev(n_blocks), dev(bloom), dev(bloom_nw),
                           dev(min_key), dev(max_key),
                           dev(starts), dev(slopes), dev(icepts), dev(nseg),
                           jnp.asarray(len(tables), jnp.int32))

    def _build_level_model(self, tree: LSMTree, level: int, model) -> LevelModel:
        tables = tree.levels[level]
        F = max(2, _next_pow2(len(tables) + 1))
        file_start = np.zeros(F, np.int64)
        acc = 0
        for i, t in enumerate(tables):
            file_start[i] = acc
            acc += t.n
        S = self.cfg.level_seg_cap
        starts = np.full(S, np.inf, np.float64)
        slopes = np.zeros(S, np.float64)
        icepts = np.zeros(S, np.float64)
        ns = 0
        if model is not None:
            ns = int(model.n_segments)
            starts[:ns] = np.asarray(model.starts)[:ns]
            slopes[:ns] = np.asarray(model.slopes)[:ns]
            icepts[:ns] = np.asarray(model.intercepts)[:ns]
        dev = jax.device_put
        return LevelModel(dev(starts), dev(slopes), dev(icepts),
                          jnp.asarray(ns, jnp.int32), dev(file_start))

    def build_state(self, tree: LSMTree, level_models=None) -> DeviceState:
        """Stack host tree to device, reusing unchanged levels (dirty tracking)."""
        levels = []
        lms = []
        level_models = level_models or [None] * N_LEVELS
        for i in range(N_LEVELS):
            ver = tree.level_version[i]
            # cache key = (level version, model epoch): id() is unsafe here
            # (the allocator reuses addresses after GC, which can serve a
            # stale LevelModel for a same-version level); the epoch is
            # monotonic per store and persisted, so it also survives reopen
            lm = level_models[i]
            if lm is not None and getattr(lm, "epoch", -1) == -1:
                lm.epoch = self._unstamped_epoch
                self._unstamped_epoch -= 1
            mver = (ver, None if lm is None else lm.epoch)
            if self._state_versions[i] != ver or i not in self._state_cache:
                self._state_cache[i] = self._build_level(tree.levels[i], self.cfg)
                self._state_versions[i] = ver
            if self._lm_versions[i] != mver or i not in self._lm_cache:
                self._lm_cache[i] = self._build_level_model(tree, i, level_models[i])
                self._lm_versions[i] = mver
            levels.append(self._state_cache[i])
            lms.append(self._lm_cache[i])
        return DeviceState(tuple(levels), tuple(lms))

    def build_filter_state(self, level_filters) -> FilterState:
        """Stack per-level host filters (core.filters.LevelFilter | None) to
        one padded (N_LEVELS, W) device array, reused while no filter epoch
        changed.  A level without a filter gets nw = 0 (probe yields
        all-True there — never prune without evidence)."""
        key = []
        for f in level_filters:
            if f is None:
                key.append(None)
                continue
            if f.epoch == -1:
                f.epoch = self._unstamped_epoch
                self._unstamped_epoch -= 1
            key.append((f.epoch, f.n_words))
        sig = tuple(key)
        if self._filter_cache is not None and self._filter_cache[0] == sig:
            return self._filter_cache[1]
        L = len(level_filters)
        W = max(1, _next_pow2(max((f.n_words for f in level_filters
                                   if f is not None), default=1)))
        bits = np.zeros((L, W), np.uint64)
        nw = np.zeros(L, np.int32)
        for i, f in enumerate(level_filters):
            if f is not None:
                bits[i, : f.n_words] = f.bits
                nw[i] = f.n_words
        fs = FilterState(jax.device_put(bits), jax.device_put(nw),
                         jax.device_put(nw > 0))
        self._filter_cache = (sig, fs)
        return fs

    def filter_probe(self, fstate: FilterState, probes: jnp.ndarray):
        """One batched filter-plane probe for the whole batch: (L, B) bool
        maybe-mask ahead of the descent (SearchFB hoisted in front of
        FindFiles).  Dispatches async like the lookup itself."""
        from repro.kernels.ops import bloom_probe_stack
        key = ("fprobe", probes.shape[0], fstate.bits.shape,
               self.cfg.filter_impl)
        if key not in self._jit_cache:
            k, impl = self.cfg.bloom_k, self.cfg.filter_impl
            self._jit_cache[key] = jax.jit(
                lambda bits, nw, p: bloom_probe_stack(bits, nw, p,
                                                      k_hashes=k, impl=impl))
        return self._jit_cache[key](fstate.bits, fstate.nw, probes)

    # ---------------------------------------------------------------- probes
    def _probe_file_baseline(self, lv: DeviceLevel, f, probes):
        cfg = self.cfg
        # SearchIB: fence compare-count -> block id (bisect_right - 1).
        # Fences padded with KEY_SENTINEL never count.
        blk = jnp.maximum(count_le_rows(lv.fences, f, probes) - 1, 0)
        # SearchFB: bloom
        maybe = bloom_probe_rows(lv.bloom, lv.bloom_nw, f, probes, cfg.bloom_k)
        # SearchDB: gather the data block (the "LoadDB" bytes), locate inside
        C = lv.keys.shape[-1]
        base = blk * cfg.block_records
        cols = jnp.clip(base[:, None]
                        + jnp.arange(cfg.block_records, dtype=jnp.int32)[None],
                        0, C - 1)
        block = lv.keys[f[:, None], cols]                 # (B, block)
        within = jnp.sum(block < probes[:, None], axis=-1).astype(jnp.int32)
        idx = base + within
        kv = lv.keys[f, jnp.clip(idx, 0, C - 1)]
        hit = maybe & (idx < lv.n[f]) & (kv == probes)
        vptr = jnp.where(hit, lv.vptrs[f, jnp.clip(idx, 0, C - 1)], -1)
        return hit, vptr

    def _probe_file_model(self, lv: DeviceLevel, f, probes):
        cfg = self.cfg
        d = cfg.plr_delta
        # ModelLookup: segment compare-count (+inf pads never count) + FMA;
        # falls back to bisect only when the segment table is wide
        S = lv.starts.shape[-1]
        if S <= 1024:
            seg = count_le_rows(lv.starts, f, probes.astype(jnp.float64)) - 1
        else:
            seg = binsearch_rows(lv.starts, f, probes.astype(jnp.float64),
                                 jnp.zeros_like(f, jnp.int32),
                                 jnp.maximum(lv.nseg[f], 1), side="right") - 1
        seg = jnp.maximum(seg, 0)
        pos = lv.slopes[f, seg] * probes.astype(jnp.float64) + lv.icepts[f, seg]
        pos = jnp.clip(jnp.round(pos).astype(jnp.int32), 0,
                       jnp.maximum(lv.n[f] - 1, 0))
        # SearchFB
        maybe = bloom_probe_rows(lv.bloom, lv.bloom_nw, f, probes, cfg.bloom_k)
        # LoadChunk + LocateKey: delta-window gather + compare
        offs = jnp.arange(-(d + 1), d + 2, dtype=jnp.int32)   # rounding slack
        C = lv.keys.shape[-1]
        win_idx = jnp.clip(pos[:, None] + offs[None, :], 0, C - 1)
        win = lv.keys[f[:, None], win_idx]                    # (B, 2d+3)
        eq = win == probes[:, None]
        hit_in = jnp.any(eq, axis=-1)
        rel = jnp.argmax(eq, axis=-1)
        idx = win_idx[jnp.arange(probes.shape[0]), rel]
        hit = maybe & hit_in & (idx < lv.n[f])
        vptr = jnp.where(hit, lv.vptrs[f, idx], -1)
        return hit, vptr

    def _probe_level_via_model(self, lv: DeviceLevel, lm: LevelModel, probes):
        """Level-model path: PLR gives a global index -> (file, local idx)."""
        cfg = self.cfg
        d = cfg.plr_delta
        B = probes.shape[0]
        zeros = jnp.zeros((B,), jnp.int32)
        seg = binsearch_rows(lm.starts[None, :], zeros,
                             probes.astype(jnp.float64), zeros,
                             jnp.broadcast_to(jnp.maximum(lm.nseg, 1), (B,)),
                             side="right") - 1
        seg = jnp.maximum(seg, 0)
        gpos = lm.slopes[seg] * probes.astype(jnp.float64) + lm.icepts[seg]
        total = jnp.sum(lv.n.astype(jnp.int64))
        gpos = jnp.clip(jnp.round(gpos).astype(jnp.int64), 0,
                        jnp.maximum(total - 1, 0))
        offs = jnp.arange(-(d + 1), d + 2, dtype=jnp.int64)
        gidx = jnp.clip(gpos[:, None] + offs[None, :], 0,
                        jnp.maximum(total - 1, 0))        # (B, 2d+3) global
        Fdim = lm.file_start.shape[0]
        nf = lv.n_files
        # global -> (file, local): file = bisect_right(file_start, g) - 1
        flat_g = gidx.reshape(-1)
        zf = jnp.zeros_like(flat_g, jnp.int32)
        fidx = binsearch_rows(lm.file_start[None, :], zf,
                              flat_g, zf,
                              jnp.broadcast_to(nf, flat_g.shape),
                              side="right") - 1
        fidx = jnp.clip(fidx, 0, Fdim - 1)
        local = flat_g - lm.file_start[fidx]
        C = lv.keys.shape[-1]
        local = jnp.clip(local, 0, C - 1).astype(jnp.int32)
        win = lv.keys[fidx, local].reshape(B, -1)
        eq = win == probes[:, None]
        hit_in = jnp.any(eq, axis=-1)
        rel = jnp.argmax(eq, axis=-1)
        sel = jnp.arange(B) * win.shape[1] + rel
        f_sel = fidx[sel]
        l_sel = local[sel]
        maybe = bloom_probe_rows(lv.bloom, lv.bloom_nw, f_sel, probes, cfg.bloom_k)
        hit = maybe & hit_in
        vptr = jnp.where(hit, lv.vptrs[f_sel, l_sel], -1)
        return hit, vptr, f_sel

    def _find_file(self, lv: DeviceLevel, probes):
        """FindFiles for a sorted level: candidate = first file with
        max_key >= probe; valid if min_key <= probe."""
        B = probes.shape[0]
        zeros = jnp.zeros((B,), jnp.int32)
        nf = jnp.broadcast_to(lv.n_files, (B,))
        f = binsearch_rows(lv.max_key[None, :], zeros, probes, zeros, nf,
                           side="left")
        Fdim = lv.max_key.shape[0]
        f_c = jnp.clip(f, 0, Fdim - 1)
        valid = (f < lv.n_files) & (lv.min_key[f_c] <= probes)
        return f_c, valid

    # ---------------------------------------------------------------- lookup
    def _lookup_impl(self, state: DeviceState, probes, mode: str,
                     l0_slots: tuple, live_levels: tuple = (True,) * N_LEVELS,
                     fmaybe=None, fhas=None, use_filters: bool = False):
        # l0_slots / live_levels / use_filters — static per jit
        # specialization; empty levels are skipped entirely (no dead
        # gathers).  fmaybe: (N_LEVELS, B) filter-plane maybe-mask; fhas:
        # (N_LEVELS,) which levels carry a real filter (for FP accounting).
        """mode: 'baseline' | 'model' | 'mixed' | 'level'."""
        self.trace_count += 1   # python side effect: runs only at trace
        B = probes.shape[0]
        found = jnp.zeros(B, bool)
        vptr = jnp.full(B, -1, jnp.int64)
        served = jnp.full(B, -1, jnp.int8)
        pos_counts, neg_counts = [], []
        prn_l, fp_l = [], []     # per-level pruned / false-positive probes

        def probe_one(lv, f, probes):
            if mode == "baseline":
                return self._probe_file_baseline(lv, f, probes)
            if mode == "model_pure":
                # every live file is learned: skip the baseline arm entirely
                return self._probe_file_model(lv, f, probes)
            hit_m, v_m = self._probe_file_model(lv, f, probes)
            has = lv.nseg[f] > 0
            hit_b, v_b = self._probe_file_baseline(lv, f, probes)
            return jnp.where(has, hit_m, hit_b), jnp.where(has, v_m, v_b)

        for li in range(N_LEVELS):
            lv = state.levels[li]
            Fdim = lv.max_key.shape[0]
            pos_c = jnp.zeros(Fdim, jnp.int32)
            neg_c = jnp.zeros(Fdim, jnp.int32)
            prn = jnp.zeros((), jnp.int64)
            fpc = jnp.zeros((), jnp.int64)
            if not live_levels[li]:
                pos_counts.append(pos_c)
                neg_counts.append(neg_c)
                prn_l.append(prn)
                fp_l.append(fpc)
                continue
            if li == 0:
                # probe each L0 slot newest-first; unrolled over static slots
                for s in range(l0_slots[0]):
                    f = jnp.full(B, s, jnp.int32)
                    in_range = ((lv.min_key[s] <= probes) &
                                (probes <= lv.max_key[s]) &
                                (s < lv.n_files))
                    active = ~found & in_range
                    if use_filters:
                        # the L0 filter row covers the union of all L0
                        # tables: a screened key skips every slot's probe
                        prn = prn + jnp.sum(active & ~fmaybe[0],
                                            dtype=jnp.int64)
                        active = active & fmaybe[0]
                    hit, v = probe_one(lv, f, probes)
                    hit = hit & active
                    if use_filters:
                        fpc = fpc + jnp.where(
                            fhas[0],
                            jnp.sum(active & ~hit, dtype=jnp.int64),
                            jnp.int64(0))
                    pos_c = pos_c.at[s].add(jnp.sum(hit, dtype=jnp.int32))
                    neg_c = neg_c.at[s].add(
                        jnp.sum(active & ~hit, dtype=jnp.int32))
                    vptr = jnp.where(hit, v, vptr)
                    served = jnp.where(hit, jnp.int8(0), served)
                    found = found | hit
            else:
                if mode == "level":
                    lm = state.level_models[li]
                    use_lm = lm.nseg > 0
                    f_cand, valid = self._find_file(lv, probes)
                    active = ~found & valid
                    if use_filters:
                        prn = prn + jnp.sum(active & ~fmaybe[li],
                                            dtype=jnp.int64)
                        active = active & fmaybe[li]
                    hit_lm, v_lm, f_lm = self._probe_level_via_model(
                        lv, lm, probes)
                    hit_b, v_b = self._probe_file_baseline(lv, f_cand, probes)
                    hit = jnp.where(use_lm, hit_lm, hit_b) & active
                    v = jnp.where(use_lm, v_lm, v_b)
                    fattr = jnp.where(use_lm, f_lm, f_cand)
                else:
                    f_cand, valid = self._find_file(lv, probes)
                    active = ~found & valid
                    if use_filters:
                        prn = prn + jnp.sum(active & ~fmaybe[li],
                                            dtype=jnp.int64)
                        active = active & fmaybe[li]
                    hit, v = probe_one(lv, f_cand, probes)
                    hit = hit & active
                    fattr = f_cand
                if use_filters:
                    fpc = fpc + jnp.where(
                        fhas[li],
                        jnp.sum(active & ~hit, dtype=jnp.int64),
                        jnp.int64(0))
                pos_c = pos_c + jax.ops.segment_sum(
                    hit.astype(jnp.int32), fattr, num_segments=Fdim)
                neg_c = neg_c + jax.ops.segment_sum(
                    (active & ~hit).astype(jnp.int32), fattr,
                    num_segments=Fdim)
                vptr = jnp.where(hit, v, vptr)
                served = jnp.where(hit, jnp.int8(li), served)
                found = found | hit
            pos_counts.append(pos_c)
            neg_counts.append(neg_c)
            prn_l.append(prn)
            fp_l.append(fpc)
        # per-level model-path vs baseline-path attribution, in-graph so
        # the host never has to materialize the per-file vectors: mirrors
        # BourbonStore._account_lookup's has-model rule per engine mode
        mps, bps = [], []
        for li in range(N_LEVELS):
            lv = state.levels[li]
            tot_f = (pos_counts[li] + neg_counts[li]).astype(jnp.int64)
            tot = jnp.sum(tot_f)
            if mode == "baseline":
                mp = jnp.int64(0)
            elif mode == "model_pure":
                mp = tot
            elif mode == "level" and li > 0:
                mp = jnp.where(state.level_models[li].nseg > 0, tot,
                               jnp.int64(0))
            else:   # mixed per-file arm (L0 in every mode, 'model' levels)
                mp = jnp.sum(jnp.where(lv.nseg > 0, tot_f, jnp.int64(0)))
            mps.append(mp)
            bps.append(tot - mp)
        probe_split = jnp.stack([jnp.stack(mps), jnp.stack(bps)], axis=1)
        filter_stats = jnp.stack([jnp.stack(prn_l), jnp.stack(fp_l)], axis=1)
        return (found, vptr, served, tuple(pos_counts), tuple(neg_counts),
                probe_split, filter_stats)

    @staticmethod
    def state_signature(state: DeviceState) -> tuple:
        """Full shape/dtype signature of a device state.  Two states with
        equal signatures are guaranteed to reuse one traced program —
        keying the jit cache on the keys-array shapes alone would let a
        state whose bloom/fence/segment padding moved silently retrace
        inside a cached wrapper."""
        return tuple((tuple(leaf.shape), str(leaf.dtype))
                     for leaf in jax.tree_util.tree_leaves(state))

    def _jitted_lookup(self, state: DeviceState, B: int, mode: str,
                       l0_live: int | None, fsig: tuple | None = None,
                       level_maybe: tuple | None = None):
        l0_cap = int(state.levels[0].max_key.shape[0])
        # bucket the L0 slot count (0 or cap): occupancy changes must not
        # retrigger compilation in mixed read/write workloads
        l0_n = 0 if (l0_live == 0) else l0_cap
        live = tuple(bool(int(lv.n_files) > 0) for lv in state.levels)
        if level_maybe is not None:
            # filter-plane hint: a level whose maybe-mask is all-False for
            # every dispatched key cannot serve any of them (zero false
            # negatives) — drop it from the traced program entirely, which
            # is where miss-heavy batches actually save wall-clock
            live = tuple(a and b for a, b in zip(live, level_maybe))
            l0_n = l0_n if live[0] else 0
        key = (mode, B, l0_n, live, fsig, self.state_signature(state))
        if key not in self._jit_cache:
            fn = partial(self._lookup_impl, mode=mode, l0_slots=(l0_n,),
                         live_levels=live)
            if fsig is None:
                self._jit_cache[key] = jax.jit(
                    lambda st, p: fn(st, p))
            else:
                self._jit_cache[key] = jax.jit(
                    lambda st, p, fm, fh: fn(st, p, fmaybe=fm, fhas=fh,
                                             use_filters=True))
        return self._jit_cache[key]

    def lookup_async(self, state: DeviceState, probes: np.ndarray, mode: str,
                     vlog=None, l0_live: int | None = None,
                     fstate: FilterState | None = None,
                     fmaybe_host: np.ndarray | None = None,
                     level_maybe: tuple | None = None) -> PendingLookup:
        """Dispatch half of the lookup: launches the device program and
        returns immediately with device-array futures (JAX async
        dispatch).  The host is free to admit/coalesce the next batch
        while this one computes; `PendingLookup.resolve()` blocks.

        With ``fstate`` the filter plane runs first: one batched probe of
        the stacked per-level filters, whose (L, B) maybe-mask prunes the
        levels the descent visits per key (still a single async dispatch
        chain — no host sync).  A caller that already hashed the batch
        host-side (the store's pre-dispatch screen) passes the mask as
        ``fmaybe_host`` so the device doesn't probe the same keys twice."""
        B = probes.shape[0]
        p_dev = jnp.asarray(probes, jnp.int64)
        if fstate is None:
            fn = self._jitted_lookup(state, B, mode, l0_live)
            (found, vptr, served, pos_c, neg_c, probe_split,
             filter_stats) = fn(state, p_dev)
        else:
            fmaybe = (jnp.asarray(fmaybe_host) if fmaybe_host is not None
                      else self.filter_probe(fstate, p_dev))
            fsig = (tuple(fstate.bits.shape), self.cfg.filter_impl)
            fn = self._jitted_lookup(state, B, mode, l0_live, fsig,
                                     level_maybe)
            (found, vptr, served, pos_c, neg_c, probe_split,
             filter_stats) = fn(state, p_dev, fmaybe, fstate.has)
        if self.record_probe_split:
            # one async device-side add per batch; the running totals are
            # synced to the host only when *_np() is called
            self.probe_split_acc = (
                probe_split if self.probe_split_acc is None
                else self.probe_split_acc + probe_split)
            if fstate is not None:
                self.filter_stats_acc = (
                    filter_stats if self.filter_stats_acc is None
                    else self.filter_stats_acc + filter_stats)
        values = None
        if self.cfg.fetch_values and vlog is not None:
            dv = vlog.device_view()
            safe = jnp.clip(vptr, 0, dv.shape[0] - 1)
            values = dv[safe]
        return PendingLookup(found, vptr, served, pos_c, neg_c, values)

    def lookup(self, state: DeviceState, probes: np.ndarray, mode: str,
               vlog=None, l0_live: int | None = None,
               fstate: FilterState | None = None) -> LookupResult:
        return self.lookup_async(state, probes, mode, vlog, l0_live,
                                 fstate).resolve()

    def probe_split_np(self) -> np.ndarray:
        """Materialize the accumulated per-level (model, baseline) probe
        counts — ONE device->host sync, meant for the snapshot path only
        (``probe_acc_materializations`` counts these so tests can assert
        the hot path never pays it)."""
        if self.probe_split_acc is None:
            return np.zeros((N_LEVELS, 2), np.int64)
        self.probe_acc_materializations += 1
        return np.asarray(self.probe_split_acc)

    def filter_stats_np(self) -> np.ndarray:
        """Materialize the accumulated per-level (pruned, false-positive)
        filter-plane counts — same one-sync snapshot-only discipline as
        probe_split_np (own counter, so probe-split sync assertions stay
        exact)."""
        if self.filter_stats_acc is None:
            return np.zeros((N_LEVELS, 2), np.int64)
        self.filter_acc_materializations += 1
        return np.asarray(self.filter_stats_acc)
