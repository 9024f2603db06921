"""Block Top-K greedy sparsification — Pallas TPU kernel.

TPU adaptation of Top-K (Def. 1, C in B(K/d)): a GLOBAL top-k needs a
full sort — hostile to the TPU memory hierarchy (multiple HBM passes,
no MXU work).  Instead each VMEM-resident block keeps its own top
``k_block = K * block/d`` elements: "block Top-K".  The contraction
property is preserved blockwise with the same delta = K/d (each block
satisfies E||C(x_b)-x_b||^2 <= (1-k_b/n_b)||x_b||^2), and empirically
block Top-K tracks global Top-K closely for i.i.d.-ish gradient noise.

In-block selection uses THRESHOLD BISECTION, not sorting: ~32 VPU-friendly
iterations of "count |x| >= t" narrow t to the k-th magnitude, then a
single masked select keeps everything above the threshold (>= k elements;
ties inflate the kept set, never shrink it — safe for a contraction).

Layout: (rows, 128) lanes; one grid step owns ``block_rows`` rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_ROWS = 64  # block = 64*128 = 8192 elements
BISECT_ITERS = 32


def _block_topk_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)
    a = jnp.abs(x)
    hi0 = jnp.max(a)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((a >= mid).astype(jnp.int32))
        keep_raising = cnt >= k
        lo = jnp.where(keep_raising, mid, lo)
        hi = jnp.where(keep_raising, hi, mid)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, BISECT_ITERS, body, (jnp.float32(0.0), hi0))
    o_ref[...] = jnp.where(a >= lo, x, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def block_topk_2d(x, *, k: int, block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret: bool = True):
    """x: (R, 128); keeps the top-k magnitudes of each (block_rows, 128)
    block (>= k on exact magnitude ties)."""
    r, lane = x.shape
    assert lane == LANE and r % block_rows == 0
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_block_topk_kernel, k=k),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="block_topk_2d",
        interpret=interpret,
    )(x)
