"""Codec-driven tree-mean collectives — the "send m_i to master, average"
line of Algorithm 1, in the wire formats the system supports.

Every payload format here is OWNED by a codec in ``repro.core.compressors``
(``encode``/``decode``/``wire_bits``); this module only moves payloads
around the mesh — it contains no compressor math of its own:

  ``dense_mean``         exact f32 mean (lowers to a plain psum under
                         GSPMD) — the no-compression baseline.
  ``randk_shared_mean``  correlated Rand-K: every worker runs
                         ``RandK(shared_pattern=True).encode`` with the
                         SAME per-step key, so the K-value payloads share
                         one pattern and aggregate by a payload mean; one
                         decode scatters the averaged values back.
                         Exactly K coordinates survive, unbiased over the
                         pattern draw.
  ``q8_ring_tree_mean``  ring all-reduce (reduce-scatter + all-gather)
                         whose hops forward ``Int8Stochastic`` payloads
                         (int8 block + f32 scale) over the mesh's worker
                         axes, with an optional quantized tree (psum)
                         stage across the ``pod`` axis.  The ring is
                         generic over any meta-free codec
                         (``_ring_allreduce_coded``); codecs that set
                         ``fused_ring`` (``kernels.q8ring.FusedQ8``)
                         take ``_ring_allreduce_fused`` instead, where
                         chunk gather + scale + int8 quantize are one
                         Pallas kernel per hop (``q8_ring_fused`` mode).

``compressed_tree_mean`` dispatches between them from an aggregation-mode
string or a ``CompressionConfig``; ``repro.comm.MeshChannel`` is the
higher-level entry point.  Every tree-level entry takes an optional
``leaf_indices`` — the GLOBAL positions of the given leaves in the full
gradient tree, so per-leaf keys stay stable when the overlap runtime
(``repro.comm.overlap``) reduces bucket subtrees independently.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm.wire import encode_meta_free, encode_workers
from repro.core.compressors import Compressor, Int8Stochastic, RandK

tmap = jax.tree_util.tree_map


def dense_mean(wtree):
    """Exact mean over the leading worker axis, leaf-wise."""
    return tmap(lambda a: jnp.mean(a, axis=0), wtree)


# ---------------------------------------------------------------------------
# Shared-pattern Rand-K
# ---------------------------------------------------------------------------


def randk_shared_mean(key: jax.Array, wtree, ratio: float, *,
                      leaf_indices: Optional[Sequence[int]] = None):
    """Mean of shared-pattern Rand-K messages (correlated sampling).

    Every worker encodes with the SAME per-leaf key, so
    ``RandK(shared_pattern=True)`` gives all workers one uniformly-random
    K-subset (K = round(ratio * d) per leaf, at least 1).  The per-worker
    payload is just the K kept values (the pattern is implied by the
    shared seed — it lives in ``meta`` and is never charged to the wire);
    the master averages payloads value-wise and decodes ONCE:

        mean_i C_shared(g_i) = decode(mean_i encode(g_i))

    (decode is linear in the values for a fixed pattern).  Unbiased over
    the pattern draw: E[(d/K) * mask] = 1 coordinatewise.
    """
    codec = RandK(q=ratio, shared_pattern=True)
    leaves, treedef = jax.tree_util.tree_flatten(wtree)
    idxs = _leaf_indices(leaves, leaf_indices)
    out = []
    for i, leaf in enumerate(leaves):
        lk = jax.random.fold_in(key, idxs[i])
        sds = jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
        payload, meta = encode_workers(codec, lk, leaf)
        mean_payload = tmap(lambda v: jnp.mean(v, axis=0), payload)
        meta_one = tmap(lambda v: v[0], meta)  # identical across workers
        out.append(codec.decode(mean_payload, meta_one, sds))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Codec ring / tree all-reduce
# ---------------------------------------------------------------------------


# the meta-free encode guard lives in comm.wire now (shared with the
# Channel layer); kept under its old private name for callers/tests
_encode_meta_free = encode_meta_free


def _leaf_indices(leaves, leaf_indices) -> tuple:
    """Normalize/validate the global leaf positions for per-leaf keys."""
    if leaf_indices is None:
        return tuple(range(len(leaves)))
    if len(leaf_indices) != len(leaves):
        raise ValueError(
            f"leaf_indices has {len(leaf_indices)} entries for "
            f"{len(leaves)} leaves"
        )
    return tuple(int(i) for i in leaf_indices)


def _ring_schedule(key: jax.Array, chunks: jax.Array, axis: str, n: int, *,
                   encode_send, decode_add, decode):
    """THE ring all-reduce schedule, in one place.

    ``chunks`` is (n, ...) with one chunk per device position; both ring
    variants (generic coded, Pallas-fused) drive this same hop/ownership
    arithmetic through three hooks:

      ``encode_send(k, chunks, chunk_id)``  encode the rotating send
            chunk into a forwardable payload pytree.
      ``decode_add(payload, mine)``         dequantize + accumulate into
            the local (1, ...) chunk slice.
      ``decode(payload)``                   dequantize to a (1, ...) slice.

    Phase 1 — reduce-scatter: at hop t each device sends chunk
    ``(idx - t) % n`` (per-hop key ``fold_in(key, t)``) and accumulates
    what it receives into chunk ``(send_id - 1) % n``; after n-1 hops
    device i owns the fully reduced chunk ``(i + 1) % n``.  Phase 2 —
    all-gather: each owner's chunk is encoded ONCE (key ``n + 1``) and
    the payload forwarded verbatim, so every device decodes
    bit-identical values — the output is truly replicated over ``axis``.
    """
    idx = jax.lax.axis_index(axis)
    fwd = [(j, (j + 1) % n) for j in range(n)]

    def hop(payload):
        return tmap(lambda a: jax.lax.ppermute(a, axis, fwd), payload)

    for t in range(n - 1):
        send_id = (idx - t) % n
        payload = hop(encode_send(jax.random.fold_in(key, t), chunks,
                                  send_id))
        recv_id = (send_id - 1) % n
        mine = jax.lax.dynamic_slice_in_dim(chunks, recv_id, 1, axis=0)
        chunks = jax.lax.dynamic_update_slice_in_dim(
            chunks, decode_add(payload, mine), recv_id, axis=0
        )

    own_id = (idx + 1) % n
    payload = encode_send(jax.random.fold_in(key, n + 1), chunks, own_id)
    final = jnp.zeros_like(chunks)
    final = jax.lax.dynamic_update_slice_in_dim(
        final, decode(payload), own_id, axis=0
    )
    for t in range(n - 1):
        payload = hop(payload)
        recv_id = (idx - t) % n  # sender (idx-1) owned (idx - t) at hop t
        final = jax.lax.dynamic_update_slice_in_dim(
            final, decode(payload), recv_id, axis=0
        )
    return final


def _ring_allreduce_coded(key: jax.Array, x: jax.Array, axis: str, n: int,
                          codec: Compressor):
    """Ring all-reduce of ``x`` (sum) over mesh axis ``axis``, forwarding
    the CODEC'S ENCODED PAYLOAD on every hop (schedule in
    ``_ring_schedule``).

    The payload pytree is permuted leaf-wise, so this works for any
    codec whose decoder state travels entirely in the payload (empty
    ``meta`` — shared-seed side information cannot ride the ring).
    """
    if n == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    d = flat.shape[0]
    c = -(-d // n)  # chunk length, ceil
    chunks = jnp.pad(flat, (0, n * c - d)).reshape(n, c)
    sds = jax.ShapeDtypeStruct((1, c), jnp.float32)
    encode = functools.partial(_encode_meta_free, codec)

    final = _ring_schedule(
        key, chunks, axis, n,
        encode_send=lambda k, ch, cid: encode(
            k, jax.lax.dynamic_slice_in_dim(ch, cid, 1, axis=0)
        ),
        decode_add=lambda p, mine: mine + codec.decode(p, {}, sds),
        decode=lambda p: codec.decode(p, {}, sds),
    )
    return final.reshape(-1)[:d].reshape(shape)


def _ring_allreduce_fused(key: jax.Array, x: jax.Array, axis: str, n: int,
                          codec):
    """Ring all-reduce with the Pallas-fused q8 hop kernels.

    Same ``_ring_schedule``, but the per-hop pipeline — gather the
    rotating send chunk, compute tile scales, stochastic-round to int8 —
    is ONE kernel (``q8_quantize_chunk_3d``: the chunk id goes in via
    scalar prefetch, so no f32 chunk copy materializes), and the receive
    side is one fused dequant-accumulate pass.  ``codec`` is a
    ``kernels.q8ring.FusedQ8`` (blockwise scales; supplies block_rows /
    interpret).  Chunks are row-aligned to the (rows, 128) lane layout.
    """
    from repro.kernels.q8ring.kernel import (
        LANE,
        q8_dequant_add_2d,
        q8_quantize_chunk_3d,
    )
    from repro.kernels.q8ring.ops import q8_dequant, ring_chunk_layout

    if n == 1:
        return x
    shape = x.shape
    d = int(x.size)
    rows_c, block = ring_chunk_layout(d, n, codec.block_rows)
    flat = x.reshape(-1).astype(jnp.float32)
    chunks = jnp.pad(flat, (0, n * rows_c * LANE - d)).reshape(
        n, rows_c, LANE
    )
    interp = codec.run_interpret

    def encode_send(k, ch, cid):
        u = jax.random.uniform(k, (rows_c, LANE))
        return q8_quantize_chunk_3d(ch, u, cid, block_rows=block,
                                    interpret=interp)

    def decode_add(payload, mine):
        q, s = payload
        return q8_dequant_add_2d(q, s, mine[0], block_rows=block,
                                 interpret=interp)[None]

    def decode(payload):
        q, s = payload
        return q8_dequant(q, s, block=block, interpret=interp)[None]

    final = _ring_schedule(
        key, chunks, axis, n,
        encode_send=encode_send, decode_add=decode_add, decode=decode,
    )
    return final.reshape(-1)[:d].reshape(shape)


def q8_ring_tree_mean(
    key: jax.Array,
    tree,
    mesh,
    *,
    worker_axes: Sequence[str] = ("data",),
    pod_axis: Optional[str] = None,
    wspecs=None,
    codec: Compressor = Int8Stochastic(),
    leaf_indices: Optional[Sequence[int]] = None,
):
    """Quantized ring/tree mean over a worker-stacked tree on a sharded
    mesh, with ``Int8Stochastic`` payloads by default.

    Leaves are ``(W, ...)`` with the leading dim sharded over
    ``worker_axes`` (plus ``pod_axis``); each device sums its local
    worker rows in f32, ring-all-reduces the partial sums over each
    worker axis with encoded hops, then (multi-pod) runs one quantized
    tree (psum) stage across ``pod_axis``.  ``wspecs`` optionally gives
    the worker-stacked PartitionSpecs so inner-dim ("model") sharding is
    preserved through the shard_map — each model shard runs its own
    independent ring.  Codecs with ``fused_ring`` set (``FusedQ8``) run
    the Pallas-fused hop pipeline instead of the generic encoded ring.
    ``leaf_indices`` pins per-leaf keys to global tree positions so a
    bucket subtree reduces bit-identically to the same leaves inside the
    full tree (the overlap runtime's drained-sync contract).
    """
    waxes = tuple(worker_axes)
    all_axes = ((pod_axis,) if pod_axis else ()) + waxes
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idxs = _leaf_indices(leaves, leaf_indices)
    ring = (_ring_allreduce_fused if getattr(codec, "fused_ring", False)
            else _ring_allreduce_coded)
    w_glob = [leaf.shape[0] for leaf in leaves]

    if wspecs is None:
        spec_leaves = [P(all_axes) for _ in leaves]
    else:
        # pair each value leaf with its spec (specs are tuple subclasses,
        # so flatten against the VALUE tree's structure), then force the
        # leading entry to the worker axes: W always divides their
        # product (n_workers == prod(worker axis sizes))
        spec_leaves = jax.tree_util.tree_leaves(
            tmap(lambda _, sp: sp, tree, wspecs),
            is_leaf=lambda x: isinstance(x, P),
        )
        spec_leaves = [P(all_axes, *tuple(sp)[1:]) for sp in spec_leaves]

    in_specs = tuple(spec_leaves)
    out_specs = tuple(P(*tuple(sp)[1:]) for sp in in_specs)
    pod_n = sizes.get(pod_axis, 1) if pod_axis else 1

    def local_fn(k, *ls):
        outs = []
        for i, x in enumerate(ls):
            lk = jax.random.fold_in(k, idxs[i])
            acc = jnp.sum(x.astype(jnp.float32), axis=0)
            for j, ax in enumerate(waxes):
                acc = ring(
                    jax.random.fold_in(lk, j), acc, ax, sizes[ax], codec
                )
            if pod_axis and pod_n > 1:
                payload = _encode_meta_free(
                    codec, jax.random.fold_in(lk, 101), acc
                )
                dec = codec.decode(
                    payload, {}, jax.ShapeDtypeStruct(acc.shape, jnp.float32)
                )
                acc = jax.lax.psum(dec, pod_axis)
            outs.append((acc / w_glob[i]).astype(x.dtype))
        return tuple(outs)

    out_leaves = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(),) + in_specs,
        out_specs=out_specs,
        check_vma=False,
    )(key, *leaves)
    return jax.tree_util.tree_unflatten(treedef, list(out_leaves))


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def compressed_tree_mean(
    wtree,
    mode,
    key: jax.Array,
    mesh=None,
    *,
    randk_q: float = 0.05,
    wspecs=None,
    leaf_indices: Optional[Sequence[int]] = None,
    q8_block_rows: Optional[int] = None,
):
    """Worker-mean of a stacked tree in the configured wire format.

    ``mode`` is an aggregation-mode string (``dense | randk_shared |
    q8_ring | q8_ring_fused``) or a ``CompressionConfig``, in which case
    its effective aggregation mode and ``randk_q`` fields are used (a
    disabled config and the ``ef21`` comm mode both aggregate densely;
    ``q8_ring_overlap`` aggregates ``q8_ring_fused``).
    ``q8_block_rows`` sets the fused codec's scale-block rows (None =
    the kernel default) — a knob the autotuner searches.  Prefer
    ``repro.comm.make_channel(...).reduce_mean`` in new code.
    """
    from repro.comm.channel import AGGREGATION_MODES, aggregation_mode_of

    given = getattr(mode, "comm_mode", mode)  # pre-normalization, for errors
    if hasattr(mode, "comm_mode"):  # CompressionConfig
        randk_q = mode.randk_q
    mode = aggregation_mode_of(mode)  # ef21/disabled normalize to dense
    if mode == "dense":
        return dense_mean(wtree)
    if mode == "randk_shared":
        return randk_shared_mean(key, wtree, randk_q,
                                 leaf_indices=leaf_indices)
    if mode in ("q8_ring", "q8_ring_fused"):
        if mesh is None:
            raise ValueError(f"{mode} needs a mesh")
        if mode == "q8_ring_fused":
            from repro.kernels.q8ring.ops import FusedQ8

            codec = (FusedQ8() if q8_block_rows is None
                     else FusedQ8(block_rows=q8_block_rows))
        else:
            codec = Int8Stochastic()
        waxes = tuple(a for a in ("data",) if a in mesh.axis_names)
        pod = "pod" if "pod" in mesh.axis_names else None
        return q8_ring_tree_mean(
            key, wtree, mesh, worker_axes=waxes, pod_axis=pod, wspecs=wspecs,
            codec=codec, leaf_indices=leaf_indices,
        )
    raise ValueError(
        f"unknown aggregation mode {mode!r} (given: {given!r}); "
        f"have {AGGREGATION_MODES}"
    )
