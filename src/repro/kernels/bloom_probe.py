"""Pallas TPU kernel: vectorized bloom-filter probe (SearchFB, Fig. 6 step 4).

k double-hash probes per key, unrolled; the packed filter words live in VMEM
(a per-file filter at 10 bits/key for <=256K records is <=320KB).  Gathers are
word-indexed loads from the VMEM-resident filter.

Two entry points:

* ``bloom_probe_pallas`` — one shared (W,) filter, (B,) probes -> (B,) maybe.
* ``bloom_probe_stack_pallas`` — a padded (L, W) stack of per-level filters
  probed by the whole batch at once -> (L, B) maybe-mask.  One kernel call
  covers every level ahead of the PLR descent; a level with ``n_words == 0``
  has no filter and yields all-True (never prune without evidence).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bloom_probe_pallas", "bloom_probe_stack_pallas"]


def _hash_pair(probes):
    kk = probes.astype(jnp.uint64)
    h1 = kk * jnp.uint64(0x9E3779B97F4A7C15)
    h1 = h1 ^ (h1 >> jnp.uint64(29))
    h2 = (kk * jnp.uint64(0xC2B2AE3D27D4EB4F)) | jnp.uint64(1)
    h2 = h2 ^ (h2 >> jnp.uint64(31))
    return h1, h2


def _bloom_kernel(nw_ref, bits_ref, probes_ref, out_ref, *, k_hashes: int):
    probes = probes_ref[...]
    bits = bits_ref[...]
    m = nw_ref[0].astype(jnp.uint64) * jnp.uint64(64)
    h1, h2 = _hash_pair(probes)
    maybe = jnp.ones(probes.shape, jnp.bool_)
    W = bits.shape[0]
    for i in range(k_hashes):
        pos = (h1 + jnp.uint64(i) * h2) % m
        widx = jnp.clip((pos >> jnp.uint64(6)).astype(jnp.int32), 0, W - 1)
        word = jnp.take(bits, widx, axis=0)
        bit = (word >> (pos & jnp.uint64(63))) & jnp.uint64(1)
        maybe = maybe & (bit == jnp.uint64(1))
    out_ref[...] = maybe


@partial(jax.jit, static_argnames=("k_hashes", "block_b", "interpret"))
def bloom_probe_pallas(bits, probes, n_words, k_hashes: int = 7,
                       block_b: int = 256, *, interpret: bool):
    """Matches core.bloom.bloom_probe_ref for a shared (W,) filter.

    Arbitrary batch sizes are supported: the probe batch is padded up to a
    multiple of ``block_b`` inside this wrapper (padded lanes are probed and
    discarded — the grid never sees a ragged block).
    """
    B = probes.shape[0]
    W = bits.shape[0]
    pad = (-B) % block_b
    if pad:
        probes = jnp.concatenate(
            [probes, jnp.zeros((pad,), probes.dtype)])
    Bp = B + pad
    nw = jnp.asarray(n_words, jnp.int32).reshape(1)
    out = pl.pallas_call(
        partial(_bloom_kernel, k_hashes=k_hashes),
        out_shape=jax.ShapeDtypeStruct((Bp,), jnp.bool_),
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((W,), lambda i: (0,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        interpret=interpret,
    )(nw, bits, probes)
    return out[:B] if pad else out


def _bloom_stack_kernel(nw_ref, bits_ref, probes_ref, out_ref, *,
                        k_hashes: int):
    probes = probes_ref[...]
    bits = bits_ref[0]                      # this level's (W,) filter words
    nw = nw_ref[0]
    no_filter = nw == 0
    m = jnp.maximum(nw, 1).astype(jnp.uint64) * jnp.uint64(64)
    h1, h2 = _hash_pair(probes)
    maybe = jnp.ones(probes.shape, jnp.bool_)
    W = bits.shape[0]
    for i in range(k_hashes):
        pos = (h1 + jnp.uint64(i) * h2) % m
        widx = jnp.clip((pos >> jnp.uint64(6)).astype(jnp.int32), 0, W - 1)
        word = jnp.take(bits, widx, axis=0)
        bit = (word >> (pos & jnp.uint64(63))) & jnp.uint64(1)
        maybe = maybe & (bit == jnp.uint64(1))
    out_ref[0, :] = maybe | no_filter


@partial(jax.jit, static_argnames=("k_hashes", "block_b", "interpret"))
def bloom_probe_stack_pallas(bits, n_words, probes, k_hashes: int = 7,
                             block_b: int = 256, *, interpret: bool):
    """Probe the whole batch against a stacked (L, W) filter plane.

    bits: (L, W) uint64 — per-level filter words, width-padded to a common W.
    n_words: (L,) int32 — each level's *build-time* word count (the hash
    modulus); 0 marks a level with no filter, which yields all-True.
    probes: (B,) int64.  Returns (L, B) bool: True = maybe present at level.
    """
    L, W = bits.shape
    B = probes.shape[0]
    pad = (-B) % block_b
    if pad:
        probes = jnp.concatenate(
            [probes, jnp.zeros((pad,), probes.dtype)])
    Bp = B + pad
    nw = jnp.asarray(n_words, jnp.int32)
    out = pl.pallas_call(
        partial(_bloom_stack_kernel, k_hashes=k_hashes),
        out_shape=jax.ShapeDtypeStruct((L, Bp), jnp.bool_),
        grid=(L, Bp // block_b),
        in_specs=[
            pl.BlockSpec((1,), lambda li, bi: (li,)),
            pl.BlockSpec((1, W), lambda li, bi: (li, 0)),
            pl.BlockSpec((block_b,), lambda li, bi: (bi,)),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda li, bi: (li, bi)),
        interpret=interpret,
    )(nw, bits, probes)
    return out[:, :B] if pad else out
