"""Pallas TPU kernel: baseline sstable search (SearchIB + SearchDB).

The WiscKey binary-search path as one kernel: fence keys (index block) are
VMEM-resident; the in-block bisect then touches one block_records-sized
region of the HBM key array per probe via a bounded dynamic-slice load — the
analogue of LevelDB loading one data block.

This kernel exists to make the baseline/model comparison fair on TPU: both
paths pay one bounded HBM->VMEM fetch; the model path's window (2*delta+3)
is ~10x smaller than a 256-record block, which is exactly the paper's
LoadData reduction (Fig. 8).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["sstable_search_pallas"]


def _search_kernel(meta_ref, fences_ref, probes_ref, keys_ref, idx_ref,
                   found_ref, *, block_records: int, fence_steps: int):
    n_blocks = jnp.maximum(meta_ref[0], 1)
    n = meta_ref[1]
    fences = fences_ref[...]
    NB = fences.shape[0]
    probes = probes_ref[...]
    BB = probes.shape[0]

    # SearchIB: bisect_right over fences (vectorized across the probe tile)
    lo = jnp.zeros(probes.shape, jnp.int32)
    hi = jnp.broadcast_to(n_blocks.astype(jnp.int32), probes.shape)

    def fence_body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        kv = jnp.take(fences, jnp.clip(mid, 0, NB - 1), axis=0)
        go_right = kv <= probes
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(go_right, hi, mid)
        return jnp.where(active, lo2, lo), jnp.where(active, hi2, hi)

    lo, _ = jax.lax.fori_loop(0, fence_steps, fence_body, (lo, hi))
    blk = jnp.maximum(lo - 1, 0)

    # SearchDB: per-probe block fetch + in-block bisect
    C = keys_ref.shape[0]
    in_steps = max(1, math.ceil(math.log2(block_records + 1)))

    def body(i, _):
        probe = probes_ref[i]
        b = blk[i]
        start = jnp.clip(b * block_records, 0, jnp.maximum(C - block_records, 0))
        block = keys_ref[pl.dslice(start, block_records)]
        lo = jnp.int32(0)
        hi = jnp.minimum(jnp.int32(block_records), n - start)

        def bs(_, lohi):
            lo, hi = lohi
            active = lo < hi
            mid = (lo + hi) >> 1
            kv = jnp.take(block, jnp.clip(mid, 0, block_records - 1), axis=0)
            go_right = kv < probe
            lo2 = jnp.where(go_right, mid + 1, lo)
            hi2 = jnp.where(go_right, hi, mid)
            return (jnp.where(active, lo2, lo), jnp.where(active, hi2, hi))

        lo, hi = jax.lax.fori_loop(0, in_steps, bs, (lo, hi))
        idx = (start + lo).astype(jnp.int32)
        kv = jnp.take(block, jnp.clip(lo, 0, block_records - 1), axis=0)
        idx_ref[i] = idx
        found_ref[i] = (idx < n) & (kv == probe) & (lo < block_records)
        return 0

    jax.lax.fori_loop(0, BB, body, 0)


@partial(jax.jit, static_argnames=("block_records", "block_b", "interpret"))
def sstable_search_pallas(fences, keys, probes, n_blocks, n,
                          block_records: int = 256, block_b: int = 256,
                          *, interpret: bool):
    """Matches kernels.ref.sstable_search_ref on found probes."""
    B = probes.shape[0]
    NB = fences.shape[0]
    assert B % block_b == 0
    fence_steps = max(1, math.ceil(math.log2(NB + 1)))
    meta = jnp.stack([jnp.asarray(n_blocks, jnp.int32),
                      jnp.asarray(n, jnp.int32)])
    idx, found = pl.pallas_call(
        partial(_search_kernel, block_records=block_records,
                fence_steps=fence_steps),
        out_shape=(jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.bool_)),
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            pl.BlockSpec((NB,), lambda i: (0,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),     # keys stay in HBM
        ],
        out_specs=(pl.BlockSpec((block_b,), lambda i: (i,)),
                   pl.BlockSpec((block_b,), lambda i: (i,))),
        interpret=interpret,
    )(meta, fences, probes, keys)
    return idx, found
