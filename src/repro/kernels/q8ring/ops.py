"""Public wrappers for the fused q8 ring kernels + the ``FusedQ8`` codec.

``FusedQ8`` is a wire codec (``repro.core.compressors`` protocol) whose
encode IS the fused Pallas kernel: int8 stochastic quantization with one
f32 scale per (block_rows, 128) tile.  Blockwise scales are strictly
tighter than ``Int8Stochastic``'s per-tensor scale (each tile's lattice
spans only that tile's max), the scale sidecar costs 32 bits per
``block_rows * 128`` int8 elements (~0.05% of the payload at the
default 64-row tile; ~0.4% at the (8, 128) hardware-floor tile), and —
the point — scale-compute, quantize, and the ring's rotating chunk
gather fuse into a single memory pass on the hop hot path
(``dist.collectives._ring_allreduce_fused``).

``fused_ring = True`` marks the codec so ``q8_ring_tree_mean`` takes the
fused ring (chunk-select folded into the kernel) instead of the generic
encoded-payload ring.  The codec also works standalone anywhere a
meta-free codec does (broadcast downlink, the pod tree stage, the
``q8_block`` registry name).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.compressors import Unbiased
from repro.kernels.q8ring.kernel import (
    DEFAULT_BLOCK_ROWS,
    LANE,
    LEVELS,
    q8_dequant_add_2d,
    q8_quantize_2d,
)
from repro.obs.trace import span

#: name scopes of the codec's non-kernel work (``repro.obs.span``): the
#: uniforms of stochastic rounding, and the f32 (rows, 128) lane layout
#: in and out of the kernels
UNIFORM_SCOPE = "q8/uniform"
LAYOUT_SCOPE = "q8/layout"


def _tile_rows(rows: int, block_rows: int):
    """THE tile rule, in one place: clamp the block to the row count
    (scalar and sub-tile inputs still get exactly one scale) and pad
    rows to a block multiple.  Interpret mode does not enforce TPU
    sublane tiling — on hardware the (8, 128) f32 tile would set the
    floor HERE, and every layout (codec encode + ring chunks) follows.
    """
    block = min(block_rows, rows)
    return -(-rows // block) * block, block


def q8_layout(d: int, block_rows: int = DEFAULT_BLOCK_ROWS):
    """(rows, block, rows_pad) for a d-element vector laid out (rows, 128)."""
    rows = max(1, -(-d // LANE))
    rows_pad, block = _tile_rows(rows, block_rows)
    return rows, block, rows_pad


def ring_chunk_layout(d: int, n: int, block_rows: int = DEFAULT_BLOCK_ROWS):
    """(rows_c, block) for an n-chunk ring over a d-element vector: the
    lane rows split n ways, each chunk padded to the same tile grid as
    ``q8_layout`` (one rule — see ``_tile_rows``)."""
    rows = max(1, -(-d // LANE))
    rows_c, block = _tile_rows(-(-rows // n), block_rows)
    return rows_c, block


def to_lanes(x, rows_pad: int):
    """Flatten + zero-pad an array to the (rows_pad, 128) kernel layout."""
    flat = jnp.ravel(x).astype(jnp.float32)
    return jnp.pad(flat, (0, rows_pad * LANE - flat.shape[0])).reshape(
        rows_pad, LANE
    )


def q8_dequant(q, scales, *, block: int, interpret: bool = True):
    """Dequantize a (R, 128) int8 block with per-tile scales: fused
    dequant-add against a zero accumulator (same single kernel serves
    both the receive-accumulate and plain-decode paths)."""
    with span(LAYOUT_SCOPE):
        acc = jnp.zeros(q.shape, jnp.float32)
    return q8_dequant_add_2d(q, scales, acc, block_rows=block,
                             interpret=interpret)


@dataclass(frozen=True)
class FusedQ8(Unbiased):
    """Blockwise-scale int8 stochastic quantization, Pallas-fused.

    Payload: int8 lanes block (padded to the tile grid) + one f32 scale
    per tile — both travel, so ``wire_bits`` is structural as usual.
    Meta-free: the ring and pod tree stages may forward the payload.
    Unbiased (stochastic rounding): omega <= d / (4 * LEVELS^2), the
    per-tensor-scale bound (blockwise scales only shrink the error).

    ``interpret=None`` (the default) resolves per backend at call time:
    compiled kernels on TPU, the Pallas interpreter everywhere else —
    so the production comm mode never silently interprets on hardware,
    and CPU tests need no flag.
    """

    block_rows: int = DEFAULT_BLOCK_ROWS
    interpret: Optional[bool] = None

    #: q8_ring_tree_mean dispatches to the chunk-fused ring on this flag
    fused_ring = True

    @property
    def run_interpret(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() != "tpu"
        return self.interpret

    def encode(self, key, x):
        d = int(x.size)
        rows, block, rows_pad = q8_layout(d, self.block_rows)
        with span(LAYOUT_SCOPE):
            x2 = to_lanes(x, rows_pad)
        with span(UNIFORM_SCOPE):
            u = jax.random.uniform(key, x2.shape)
        q, scales = q8_quantize_2d(
            x2, u, block_rows=block, interpret=self.run_interpret
        )
        return {"q": q, "scale": scales}, {}

    def decode(self, payload, meta, shape_dtype):
        d = 1
        for s in shape_dtype.shape:
            d *= s
        nb = payload["scale"].shape[0]
        block = payload["q"].shape[0] // nb
        out = q8_dequant(payload["q"], payload["scale"], block=block,
                         interpret=self.run_interpret)
        with span(LAYOUT_SCOPE):
            return (
                jnp.ravel(out)[:d]
                .reshape(shape_dtype.shape)
                .astype(shape_dtype.dtype)
            )

    def omega(self, d):
        return d / (4.0 * LEVELS**2)
