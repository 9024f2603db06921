"""Fused shifted natural-compression estimator — Pallas TPU kernel.

Computes the paper's shifted gradient estimator (eq. 3) in ONE pass over
HBM:

    out = h + C_nat(g - h)

where C_nat is natural compression (stochastic rounding to powers of two,
Horváth et al. 2019a; omega = 1/8).  Unfused, this is 4+ elementwise
passes over two model-sized tensors (diff, abs/log2/exp2 lattice, round,
add-back); fused it is one read of (g, h, u) and one write — the op is
perfectly memory-bound, so the fusion is the entire win.

Randomness enters as a precomputed uniform tensor ``u`` (one f32 per
element) so the kernel is deterministic given inputs and identical under
``interpret=True`` on CPU — in-kernel ``pltpu.prng_random_bits`` would
tie validation to TPU hardware.

Layout: inputs are reshaped to (rows, 128) by ``ops.py``; the grid tiles
rows in blocks of ``block_rows`` (sublane-aligned, default 256 rows →
128 KiB f32 per operand tile in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_ROWS = 256


def _shifted_natural_kernel(g_ref, h_ref, u_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    u = u_ref[...]
    x = g - h
    a = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 1e-38)))
    lo = jnp.exp2(e)
    p_hi = a / lo - 1.0                       # in [0, 1)
    q = jnp.where(u < p_hi, 2.0 * lo, lo)
    q = jnp.where(a == 0.0, 0.0, q) * jnp.sign(x)
    o_ref[...] = (h + q).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def shifted_natural_2d(g, h, u, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                       interpret: bool = True):
    """g, h: (R, 128) same dtype; u: (R, 128) f32 in [0,1)."""
    r, lane = g.shape
    assert lane == LANE and g.shape == h.shape == u.shape
    assert r % block_rows == 0
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _shifted_natural_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        name="shifted_natural_2d",
        interpret=interpret,
    )(g, h, u)
