"""Pure-jnp oracles for every kernel in this package.

These are the contracts the Pallas kernels must match bit-for-bit (exact
integer outputs; float64 position math).  The engine (core/engine.py) calls
these on CPU; on TPU the ops.py wrappers dispatch to the Pallas versions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["plr_lookup_ref", "bounded_search_ref", "bloom_probe_kernel_ref",
           "bloom_probe_stack_ref", "sstable_search_ref"]


def _bisect(keys: jnp.ndarray, probes: jnp.ndarray, hi0: jnp.ndarray,
            side: str) -> jnp.ndarray:
    """Vectorized bisect of (B,) probes into a single sorted (N,) array."""
    N = keys.shape[0]
    steps = max(1, math.ceil(math.log2(N + 1)))
    lo = jnp.zeros(probes.shape, jnp.int32)
    hi = jnp.broadcast_to(hi0.astype(jnp.int32), probes.shape)

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        kv = keys[jnp.clip(mid, 0, N - 1)]
        go_right = (kv < probes) if side == "left" else (kv <= probes)
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(go_right, hi, mid)
        return jnp.where(active, lo2, lo), jnp.where(active, hi2, hi)

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def plr_lookup_ref(starts: jnp.ndarray, slopes: jnp.ndarray,
                   icepts: jnp.ndarray, nseg: jnp.ndarray,
                   probes: jnp.ndarray, n_max: jnp.ndarray) -> jnp.ndarray:
    """ModelLookup: segment bisect_right + FMA -> clamped int32 position.

    starts/slopes/icepts: (S,) f64 (+inf padded); nseg: () int32;
    probes: (B,) int64; n_max: () int32 (file record count).
    """
    p = probes.astype(jnp.float64)
    seg = _bisect(starts, p, jnp.maximum(nseg, 1), side="right") - 1
    seg = jnp.maximum(seg, 0)
    pos = slopes[seg] * p + icepts[seg]
    return jnp.clip(jnp.round(pos).astype(jnp.int32), 0,
                    jnp.maximum(n_max - 1, 0))


def bounded_search_ref(keys: jnp.ndarray, pos: jnp.ndarray,
                       probes: jnp.ndarray, n: jnp.ndarray,
                       delta: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """LoadChunk + LocateKey: probe the delta-window around predicted pos.

    keys: (C,) int64 sorted (+SENTINEL pad); pos: (B,) int32; n: () int32.
    Returns (idx (B,) int32, found (B,) bool).
    """
    C = keys.shape[0]
    offs = jnp.arange(-(delta + 1), delta + 2, dtype=jnp.int32)
    win_idx = jnp.clip(pos[:, None] + offs[None, :], 0, C - 1)
    win = keys[win_idx]
    eq = win == probes[:, None]
    found = jnp.any(eq, axis=-1)
    rel = jnp.argmax(eq, axis=-1)
    idx = win_idx[jnp.arange(probes.shape[0]), rel]
    found = found & (idx < n)
    return idx.astype(jnp.int32), found


def bloom_probe_kernel_ref(bits: jnp.ndarray, probes: jnp.ndarray,
                           k_hashes: int, n_words: jnp.ndarray) -> jnp.ndarray:
    """Shared-filter bloom probe (same math as core.bloom.bloom_probe_ref)."""
    from repro.core.bloom import bloom_probe_ref
    return bloom_probe_ref(bits, probes, k_hashes, n_words=n_words)


def bloom_probe_stack_ref(bits: jnp.ndarray, n_words: jnp.ndarray,
                          probes: jnp.ndarray,
                          k_hashes: int) -> jnp.ndarray:
    """Filter-plane probe: (L, W) stacked filters x (B,) probes -> (L, B).

    ``n_words[l] == 0`` marks a level with no filter (all-True row); the
    hash modulus is each level's build-time word count, never the padded W.
    """
    from repro.core.bloom import bloom_hashes, bloom_word_bit
    L, W = bits.shape
    nw = jnp.asarray(n_words, jnp.int32)
    widx, b = bloom_word_bit(bloom_hashes(probes, k_hashes)[:, None, :],
                             jnp.maximum(nw, 1)[:, None])     # (k, L, B)
    word = bits[jnp.arange(L)[None, :, None], jnp.clip(widx, 0, W - 1)]
    maybe = jnp.all((word >> b) & jnp.uint64(1) == jnp.uint64(1), axis=0)
    return maybe | (nw == 0)[:, None]


def sstable_search_ref(fences: jnp.ndarray, keys: jnp.ndarray,
                       probes: jnp.ndarray, n_blocks: jnp.ndarray,
                       n: jnp.ndarray, block_records: int
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Baseline path: SearchIB (fence bisect) + SearchDB (in-block bisect).

    fences: (NB,) int64; keys: (C,) int64; probes: (B,) int64.
    Returns (idx (B,) int32, found (B,) bool).
    """
    C = keys.shape[0]
    blk = _bisect(fences, probes, jnp.maximum(n_blocks, 1), side="right") - 1
    blk = jnp.maximum(blk, 0)
    lo = blk * block_records
    hi = jnp.minimum(lo + block_records, n)
    # bisect within [lo, hi)
    steps = max(1, math.ceil(math.log2(block_records + 1)))
    lo_ = lo.astype(jnp.int32)
    hi_ = hi.astype(jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        kv = keys[jnp.clip(mid, 0, C - 1)]
        go_right = kv < probes
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(go_right, hi, mid)
        return jnp.where(active, lo2, lo), jnp.where(active, hi2, hi)

    idx, _ = jax.lax.fori_loop(0, steps, body, (lo_, hi_))
    kv = keys[jnp.clip(idx, 0, C - 1)]
    found = (idx < n) & (kv == probes)
    return idx.astype(jnp.int32), found
