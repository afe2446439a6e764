"""Pallas TPU kernel: batched PLR inference (ModelLookup, paper Fig. 6 step 3).

Per probe: bisect the segment-start array (resident in VMEM — a file model is
a few KB), then one FMA, then clamp.  Probes are tiled over the grid; the
model arrays are broadcast to every grid step.

TPU adaptation notes (DESIGN.md §2): key math is f64 — on TPU v5e 64-bit is
emulated by Mosaic, acceptable for this non-MXU lookup path; the segment
bisect uses gather steps over a VMEM-resident vector.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["plr_lookup_pallas"]


def _plr_kernel(nseg_ref, nmax_ref, starts_ref, slopes_ref, icepts_ref,
                probes_ref, out_ref, *, steps: int):
    probes = probes_ref[...]                      # (BB,) int64
    starts = starts_ref[...]                      # (S,) f64
    nseg = jnp.maximum(nseg_ref[0], 1)
    p = probes.astype(jnp.float64)

    S = starts.shape[0]
    lo = jnp.zeros(probes.shape, jnp.int32)
    hi = jnp.broadcast_to(nseg.astype(jnp.int32), probes.shape)

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        kv = jnp.take(starts, jnp.clip(mid, 0, S - 1), axis=0)
        go_right = kv <= p                        # bisect_right
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(go_right, hi, mid)
        return jnp.where(active, lo2, lo), jnp.where(active, hi2, hi)

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    seg = jnp.maximum(lo - 1, 0)
    slope = jnp.take(slopes_ref[...], seg, axis=0)
    icept = jnp.take(icepts_ref[...], seg, axis=0)
    pos = slope * p + icept
    nmax = nmax_ref[0]
    out_ref[...] = jnp.clip(jnp.round(pos).astype(jnp.int32), 0,
                            jnp.maximum(nmax - 1, 0))


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def plr_lookup_pallas(starts, slopes, icepts, nseg, probes, n_max,
                      block_b: int = 256, *, interpret: bool):
    """Matches kernels.ref.plr_lookup_ref exactly."""
    B = probes.shape[0]
    S = starts.shape[0]
    assert B % block_b == 0, (B, block_b)
    steps = max(1, math.ceil(math.log2(S + 1)))
    grid = (B // block_b,)
    nseg_a = jnp.asarray(nseg, jnp.int32).reshape(1)
    nmax_a = jnp.asarray(n_max, jnp.int32).reshape(1)
    return pl.pallas_call(
        partial(_plr_kernel, steps=steps),
        out_shape=jax.ShapeDtypeStruct((B,), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),       # nseg (scalar prefetch)
            pl.BlockSpec((1,), lambda i: (0,)),       # nmax
            pl.BlockSpec((S,), lambda i: (0,)),       # starts, whole model in VMEM
            pl.BlockSpec((S,), lambda i: (0,)),       # slopes
            pl.BlockSpec((S,), lambda i: (0,)),       # icepts
            pl.BlockSpec((block_b,), lambda i: (i,)),  # probe tile
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        interpret=interpret,
    )(nseg_a, nmax_a, starts, slopes, icepts, probes)
