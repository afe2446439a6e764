"""Per-sstable bloom filters, vectorized.

Build is host-side numpy (at flush/compaction time, like LevelDB's filter
block); probe is a pure-jnp batched function (the TPU data plane), mirrored by
the Pallas kernel in ``repro.kernels.bloom_probe``.

Hashing: double hashing h1 + i*h2 (Kirsch-Mitzenmacher) over 64-bit
Fibonacci-mixed keys — branch-free and gather-only, which is what the VPU
wants.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["bloom_build_np", "bloom_probe_np", "bloom_probe_hashed_np",
           "bloom_probe_ref", "bloom_hashes", "bloom_word_bit",
           "bloom_words", "DEFAULT_BITS_PER_KEY"]

DEFAULT_BITS_PER_KEY = 10
_MIX1 = np.uint64(0x9E3779B97F4A7C15)
_MIX2 = np.uint64(0xC2B2AE3D27D4EB4F)


def bloom_words(n_keys: int, bits_per_key: int = DEFAULT_BITS_PER_KEY) -> int:
    """Number of uint64 words for n_keys (rounded up, min 1)."""
    bits = max(64, n_keys * bits_per_key)
    return (bits + 63) // 64


def _hash2_np(keys: np.ndarray):
    k = keys.astype(np.uint64)
    h1 = (k * _MIX1)
    h1 ^= h1 >> np.uint64(29)
    h2 = (k * _MIX2) | np.uint64(1)
    h2 ^= h2 >> np.uint64(31)
    return h1, h2


def bloom_build_np(keys: np.ndarray, n_words: int, k_hashes: int = 7) -> np.ndarray:
    """Build packed filter bits (uint64 words) for the given keys."""
    bits = np.zeros(n_words, dtype=np.uint64)
    if keys.size == 0:
        return bits
    m = np.uint64(n_words * 64)
    h1, h2 = _hash2_np(keys)
    for i in range(k_hashes):
        pos = (h1 + np.uint64(i) * h2) % m
        np.bitwise_or.at(bits, (pos >> np.uint64(6)).astype(np.int64),
                         np.uint64(1) << (pos & np.uint64(63)))
    return bits


def bloom_probe_np(bits: np.ndarray, probes: np.ndarray, k_hashes: int = 7,
                   n_words: int | None = None) -> np.ndarray:
    """Host-side numpy probe of one (W,) filter — the store's pre-dispatch
    screen (no device work, no transfers).  Same math as bloom_probe_ref."""
    h1, h2 = _hash2_np(probes)
    return bloom_probe_hashed_np(bits, h1, h2, k_hashes, n_words)


def bloom_probe_hashed_np(bits: np.ndarray, h1: np.ndarray, h2: np.ndarray,
                          k_hashes: int = 7,
                          n_words: int | None = None) -> np.ndarray:
    """Probe with pre-mixed hashes: the double-hash bases are filter-
    independent, so a multi-level screen mixes the batch once and probes
    every level's filter with the same (h1, h2)."""
    if n_words is None:
        n_words = bits.shape[0]
    m = np.uint64(int(n_words) * 64)
    maybe = np.ones(h1.shape, bool)
    for i in range(k_hashes):
        pos = (h1 + np.uint64(i) * h2) % m
        word = bits[(pos >> np.uint64(6)).astype(np.int64)]
        maybe &= ((word >> (pos & np.uint64(63))) & np.uint64(1)).astype(bool)
    return maybe


def bloom_hashes(probes: jnp.ndarray, k_hashes: int) -> jnp.ndarray:
    """(k_hashes, *probes.shape) uint64 hash values h1 + i*h2 of each key
    (the device twin of ``_hash2_np``)."""
    kk = probes.astype(jnp.uint64)
    h1 = kk * jnp.uint64(_MIX1)
    h1 = h1 ^ (h1 >> jnp.uint64(29))
    h2 = (kk * jnp.uint64(_MIX2)) | jnp.uint64(1)
    h2 = h2 ^ (h2 >> jnp.uint64(31))
    i = jnp.arange(k_hashes, dtype=jnp.uint64).reshape((-1,) + (1,) * kk.ndim)
    return h1 + i * h2


def bloom_word_bit(x: jnp.ndarray, n_words: jnp.ndarray):
    """Word index and bit index of hash value ``x`` (uint64) in a filter of
    ``n_words`` 64-bit words — the position ``x mod (64 * n_words)`` as
    (``(x >> 6) mod n_words``, ``x mod 64``), the same bits the host build
    sets.

    The TPU compiler emulates 64-bit division, and one uint64 ``%`` costs
    it tens of seconds of compile time.  So the remainder is a restoring
    division over the 58 bits of ``x >> 6``, one bit per loop step, in
    32-bit lanes: with ``n_words < 2**31`` the running remainder stays
    below ``2**32``.  Stack every hash of a probe into ``x`` so that one
    loop serves them all."""
    y = x >> jnp.uint64(6)
    n = jnp.asarray(n_words).astype(jnp.uint32)

    def step(j, r):
        b = (y >> (57 - j).astype(jnp.uint64)) & jnp.uint64(1)
        r = (r << 1) | b.astype(jnp.uint32)
        return jnp.where(r >= n, r - n, r)

    r = jax.lax.fori_loop(
        0, 58, step, jnp.zeros(jnp.broadcast_shapes(x.shape, n.shape),
                               jnp.uint32))
    return r.astype(jnp.int32), x & jnp.uint64(63)


def bloom_probe_ref(bits: jnp.ndarray, probes: jnp.ndarray, k_hashes: int = 7,
                    n_words=None) -> jnp.ndarray:
    """Pure-jnp batched probe.

    bits: (W,) shared filter, or (B, W) per-probe filter rows (padded).
    probes: (B,) int64 keys.
    n_words: live word count (scalar or (B,)) — the hash modulus must use the
    filter's *build-time* size, not the padded width.
    Returns bool (B,): True = maybe present.
    """
    if n_words is None:
        n_words = bits.shape[-1]
    widx, b = bloom_word_bit(bloom_hashes(probes, k_hashes),
                             jnp.broadcast_to(jnp.asarray(n_words),
                                              probes.shape))   # (k, B)
    if bits.ndim == 1:
        word = bits[widx]
    else:
        word = bits[jnp.arange(probes.shape[0])[None, :], widx]
    return jnp.all((word >> b) & jnp.uint64(1) == jnp.uint64(1), axis=0)
