"""Fused int8 quantize + ring-hop chunk select — Pallas TPU kernels.

The q8 ring all-reduce (``dist.collectives``) spends its per-hop time in
pure memory traffic: slice the rotating send chunk out of the local
buffer, compute a quantization scale, stochastic-round to int8, and (on
receive) dequantize and accumulate.  Unfused that is 4+ elementwise
passes over the f32 chunk plus a materialized f32 copy for the slice;
fused it is ONE read of the chunk and one s8 write per hop:

  ``_quantize_tiles``          per-tile max-|x| scale + unbiased
        stochastic rounding to int8 in a single pass.  Scales are
        per (block_rows, 128) TILE, not per tensor — strictly tighter
        than ``Int8Stochastic``'s per-tensor scale, and the scale
        reduction never needs a second pass over HBM.
  ``q8_quantize_chunk_3d``     the ring-hop variant: the send chunk
        rotates every hop (send_id = (device - t) mod n), so the chunk
        GATHER is folded into the kernel's block index_map via a
        scalar-prefetch chunk id — the f32 chunk copy that
        ``dynamic_slice`` would materialize never exists.
  ``_q8_dequant_add_kernel``   receive side: dequantize + accumulate
        into the reduction buffer in one pass (acc + q * scale).

Randomness enters as a precomputed uniform tensor (one f32 per element)
so kernels are deterministic given inputs and identical under
``interpret=True`` on CPU — in-kernel ``pltpu.prng_random_bits`` would
tie validation to TPU hardware (same policy as ``kernels.natural``).

Layout: (rows, 128) lanes; the 3-d chunk variant sees the ring buffer
as (n_chunks, rows, 128).  Two granularities, kept apart:

  scale tile   ``block_rows`` x 128 elements share one f32 scale (the
        codec's accuracy and wire format; 64 rows by default);
  grid block   the rows one grid step DMAs and processes: ``T`` whole
        scale tiles (``tiles_per_step``), each quantized on its own
        inside the step.  A grid step has a near-fixed cost (starting
        and waiting on its DMAs, about 0.3 us on a TPU v5e), so a block
        of one 32 KiB tile would bind the kernels to the step count, not
        to HBM; ``T`` tiles move about ``GRID_BLOCK_BYTES`` of f32 per
        operand.  The last block may be ragged: a tile's scale reads
        only its own rows, and writes past the array's end are dropped.

Scales cross the kernel boundary LANE-DENSE: one (1, 128) row per tile
(the scale broadcast over the lanes) in an (n_tiles, 1, 128) array, a
(T, 1, 128) block per grid step.  Mosaic accepts that block (its last
two dims equal the array's) where a (1, 1) block over an (n_tiles, 1)
array breaks the (8, 128) tiling rule, and a vector store works for a
1-row tile where a scalar store to VMEM does not.  The public wrappers
keep the compact (n_tiles, 1) scales that travel on the wire.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_BLOCK_ROWS = 64   # rows per scale tile: 64*128 f32 = 32 KiB
LEVELS = 127              # int8 quantization lattice [-127, 127]
SCALE_FLOOR = 1e-30       # well above subnormal: tiny/LEVELS must not flush
GRID_BLOCK_BYTES = 512 * 1024   # f32 bytes per operand per grid step


def tiles_per_step(n_tiles: int, block_rows: int) -> int:
    """THE grid-block rule: scale tiles per grid step, from the shape
    alone.  About ``GRID_BLOCK_BYTES`` of f32 per operand (16 tiles of
    64 rows), at least one tile, clamped to ``n_tiles`` (a one-tile leaf
    keeps one tile per step)."""
    t = GRID_BLOCK_BYTES // (block_rows * LANE * 4)
    return min(max(t, 1), n_tiles)


def _tile_rows(t: int, block_rows: int):
    return pl.ds(t * block_rows, block_rows)


def _quantize_tiles(block_rows, x_ref, u_ref, q_ref, s_ref):
    """Per scale tile t of the block: scale = max|x|/LEVELS, q =
    stochastic_round(x/scale).  The scale is kept (1, 1) so it
    broadcasts as a vector and stores lane-dense into row t."""
    for t in range(s_ref.shape[0]):
        rows = _tile_rows(t, block_rows)
        x = x_ref[rows, :].astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x), keepdims=True),
                            SCALE_FLOOR) / LEVELS
        y = x / scale
        lo = jnp.floor(y)
        up = (u_ref[rows, :] < (y - lo)).astype(jnp.float32)
        q_ref[rows, :] = (lo + up).astype(jnp.int8)
        s_ref[t] = jnp.broadcast_to(scale, (1, LANE))


def _q8_chunk_kernel(block_rows, cid_ref, x_ref, u_ref, q_ref, s_ref):
    """Chunk-select variant: x_ref is the block of the chunk picked by
    the scalar-prefetch id (see index_map below)."""
    _quantize_tiles(block_rows, x_ref, u_ref, q_ref, s_ref)


def _q8_dequant_add_kernel(block_rows, q_ref, s_ref, acc_ref, o_ref):
    for t in range(s_ref.shape[0]):
        rows = _tile_rows(t, block_rows)
        o_ref[rows, :] = (acc_ref[rows, :]
                          + q_ref[rows, :].astype(jnp.float32)
                          * s_ref[t])


def _scale_spec(t: int, index_map):
    """Block of the lane-dense scale array: the (1, 128) rows of a grid
    block's T tiles."""
    return pl.BlockSpec((t, 1, LANE), index_map)


def _scale_shape(n_tiles: int):
    return jax.ShapeDtypeStruct((n_tiles, 1, LANE), jnp.float32)


def _compact(scales3):
    """(n_tiles, 1, 128) lane-dense scales -> the (n_tiles, 1) wire form."""
    return scales3[:, :, 0]


def _grid(r: int, block_rows: int):
    """(n_tiles, T, grid steps) for r rows in scale tiles of block_rows."""
    assert r % block_rows == 0
    n_tiles = r // block_rows
    t = tiles_per_step(n_tiles, block_rows)
    return n_tiles, t, pl.cdiv(n_tiles, t)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def q8_quantize_2d(x, u, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = True):
    """x: (R, 128) f32; u: (R, 128) uniforms.  Returns
    (q: (R, 128) int8, scales: (R//block_rows, 1) f32) — one scale per
    row-block tile."""
    r, lane = x.shape
    assert lane == LANE and u.shape == x.shape
    n_tiles, t, steps = _grid(r, block_rows)
    rows = pl.BlockSpec((t * block_rows, LANE), lambda i: (i, 0))
    q, s3 = pl.pallas_call(
        functools.partial(_quantize_tiles, block_rows),
        grid=(steps,),
        in_specs=[rows, rows],
        out_specs=[rows, _scale_spec(t, lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.int8),
            _scale_shape(n_tiles),
        ],
        name="q8_quantize_2d",
        interpret=interpret,
    )(x, u)
    return q, _compact(s3)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def q8_quantize_chunk_3d(chunks, u, chunk_id, *,
                         block_rows: int = DEFAULT_BLOCK_ROWS,
                         interpret: bool = True):
    """Fused ring-hop gather + quantize.

    chunks: (n, R, 128) f32 ring buffer; chunk_id: int32 scalar (may be
    traced — it is the rotating send id inside the ring loop); u:
    (R, 128) uniforms.  Quantizes ONLY chunk ``chunk_id``: the block
    index_map reads the scalar-prefetch id, so the gather happens in the
    kernel's DMA and no f32 chunk copy is materialized.  Returns the
    same (q, scales) pair as ``q8_quantize_2d`` on ``chunks[chunk_id]``.
    """
    n, r, lane = chunks.shape
    assert lane == LANE and u.shape == (r, lane)
    n_tiles, t, steps = _grid(r, block_rows)
    rows = pl.BlockSpec((t * block_rows, LANE), lambda i, cid: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((None, t * block_rows, LANE),
                         lambda i, cid: (cid[0], i, 0)),
            rows,
        ],
        out_specs=[rows, _scale_spec(t, lambda i, cid: (i, 0, 0))],
    )
    q, s3 = pl.pallas_call(
        functools.partial(_q8_chunk_kernel, block_rows),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, LANE), jnp.int8),
            _scale_shape(n_tiles),
        ],
        name="q8_quantize_chunk_3d",
        interpret=interpret,
    )(jnp.asarray(chunk_id, jnp.int32).reshape(1), chunks, u)
    return q, _compact(s3)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def q8_dequant_add_2d(q, scales, acc, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: bool = True):
    """acc + dequant(q, scales) in one pass.  q: (R, 128) int8, scales:
    (R//block_rows, 1) f32, acc: (R, 128) f32."""
    r, lane = q.shape
    assert lane == LANE and acc.shape == q.shape
    n_tiles, t, steps = _grid(r, block_rows)
    assert scales.shape == (n_tiles, 1)
    rows = pl.BlockSpec((t * block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_q8_dequant_add_kernel, block_rows),
        grid=(steps,),
        in_specs=[rows, _scale_spec(t, lambda i: (i, 0, 0)), rows],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        name="q8_dequant_add_2d",
        interpret=interpret,
    )(q, jnp.broadcast_to(scales[:, :, None], (n_tiles, 1, LANE)), acc)
