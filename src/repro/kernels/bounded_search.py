"""Pallas TPU kernel: delta-window probe (LoadChunk + LocateKey, Fig. 6 5-6).

This is the TPU-native analogue of Bourbon's small chunk read: instead of a
4KB disk block, each probe DMAs a (2*delta+3)-record window around the PLR
prediction from the HBM-resident key array into VMEM and does a vectorized
compare.  The window is the paper's error-bound guarantee made physical:
delta bounds the bytes moved per lookup.

The sorted key array stays in ANY/HBM memory space; per-probe windows are
fetched with dynamic slices inside the kernel (async copy on real TPU,
emulated in interpret mode).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bounded_search_pallas"]


def _bounded_kernel(n_ref, pos_ref, probes_ref, keys_ref, idx_ref, found_ref,
                    *, delta: int, win: int):
    C = keys_ref.shape[0]
    n = n_ref[0]
    BB = pos_ref.shape[0]

    def body(i, _):
        pos = pos_ref[i]
        probe = probes_ref[i]
        start = jnp.clip(pos - (delta + 1), 0, jnp.maximum(C - win, 0))
        window = keys_ref[pl.dslice(start, win)]   # bounded DMA
        eq = window == probe
        hit = jnp.any(eq)
        rel = jnp.argmax(eq)
        idx = (start + rel).astype(jnp.int32)
        idx_ref[i] = idx
        found_ref[i] = hit & (idx < n)
        return 0

    jax.lax.fori_loop(0, BB, body, 0)


@partial(jax.jit, static_argnames=("delta", "block_b", "interpret"))
def bounded_search_pallas(keys, pos, probes, n, delta: int = 8,
                          block_b: int = 256, *, interpret: bool):
    """Matches kernels.ref.bounded_search_ref (idx may differ only when the
    same key appears at the window edge twice — keys are unique, so exact)."""
    B = probes.shape[0]
    C = keys.shape[0]
    assert B % block_b == 0
    win = 2 * delta + 3
    # round window to a lane-friendly multiple of 8 (int64 sublane packing)
    win = -(-win // 8) * 8
    win = min(win, C)
    grid = (B // block_b,)
    n_a = jnp.asarray(n, jnp.int32).reshape(1)
    idx, found = pl.pallas_call(
        partial(_bounded_kernel, delta=delta, win=win),
        out_shape=(jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.bool_)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),     # keys stay in HBM
        ],
        out_specs=(pl.BlockSpec((block_b,), lambda i: (i,)),
                   pl.BlockSpec((block_b,), lambda i: (i,))),
        interpret=interpret,
    )(n_a, pos, probes, keys)
    return idx, found
