"""Shared per-worker encode plumbing for the wire layer.

One home for the helpers that were duplicated between the Channel
uplink (``repro.comm.channel``) and the codec-driven collectives
(``repro.dist.collectives``): worker key derivation, the per-worker
map (``on_workers``) and encode, and the meta-free guard for
forwarded-payload transports.  Imports only jax — safe for both sides of the
comm <-> dist boundary.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def leaf_key(key: jax.Array, leaf_index: int) -> jax.Array:
    """THE per-leaf key derivation of the whole wire layer.

    Every consumer — ``Channel.uplink``/``broadcast``,
    ``ShiftRule.message``, the bucketed loops in ``comm.overlap``, and
    the codec-driven collectives — folds the leaf's GLOBAL tree
    position through this one function.  That shared derivation is what
    makes any re-schedule (bucket partition, interleaved
    message/reduce) bit-exact with the whole-tree round; change it here
    or nowhere.
    """
    return jax.random.fold_in(key, leaf_index)


def worker_keys(codec, key: jax.Array, w: int) -> jax.Array:
    """Per-worker encode keys for ONE leaf, stacked (w, *key.shape).

    Every worker samples the SAME key when the codec declares a shared
    pattern (correlated Rand-K) or is deterministic — the property the
    payload-shrinking collectives rely on; decorrelated split keys
    otherwise.
    """
    if getattr(codec, "shared_pattern", False) or not codec.stochastic:
        return jnp.broadcast_to(key, (w, *key.shape))
    return jax.random.split(key, w)


def on_workers(fn, *args, in_axes=0):
    """``jax.vmap(fn, in_axes)(*args)`` over the leading worker axis W.

    Under an ambient data-parallel mesh — worker axes ("pod", "data")
    that split W, every other axis of size 1 — the map runs inside a
    ``shard_map`` over the worker axes, so each device maps only its own
    workers' rows.  GSPMD cannot partition a Pallas (Mosaic) kernel
    such as the fused q8 codec's; the manual map needs no partitioning.
    Without such a mesh (CPU tests, tensor-parallel meshes) it is the
    plain vmap.  Every output is worker-stacked.
    """
    mapped = jax.vmap(fn, in_axes=in_axes)
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(mesh.shape) if not mesh.empty else {}
    waxes = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    axes = in_axes if isinstance(in_axes, tuple) else (in_axes,) * len(args)
    w = next(jax.tree_util.tree_leaves(x)[0].shape[0]
             for x, ax in zip(args, axes) if ax == 0)
    if (not waxes or w % math.prod(sizes[a] for a in waxes)
            or any(n > 1 for a, n in sizes.items() if a not in waxes)):
        return mapped(*args)
    spec = P(waxes)
    return jax.shard_map(
        mapped,
        in_specs=tuple(spec if ax == 0 else P() for ax in axes),
        out_specs=spec,
        check_vma=False,
    )(*args)


def encode_workers(codec, key: jax.Array, leaf: jax.Array):
    """Encode each worker row of a worker-stacked leaf.

    Returns the worker-stacked ``(payload, meta)`` pytrees (leaves gain
    a leading W axis; for shared-pattern codecs every row is encoded
    with the same key, so meta rows are identical).
    """
    return on_workers(codec.encode, worker_keys(codec, key, leaf.shape[0]),
                      leaf)


def encode_decode_workers(codec, key: jax.Array, leaf: jax.Array):
    """One uplink leaf: encode then decode each worker row.

    Returns ``(stacked payload, stacked decoded messages)`` — the
    decoded tensor is what the master-side aggregation sees, the payload
    is what wire accounting charges.
    """
    sds = jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)

    def enc_dec(k, row):
        payload, meta = codec.encode(k, row)
        return payload, codec.decode(payload, meta, sds)

    return on_workers(enc_dec, worker_keys(codec, key, leaf.shape[0]), leaf)


def encode_meta_free(codec, key: jax.Array, block: jax.Array):
    """Encode for forwarded-payload transports (ring hops, the pod psum
    stage): the decoder sees ONLY the payload, so shared-seed side
    information in ``meta`` cannot travel — reject codecs that need it.
    """
    payload, meta = codec.encode(key, block)
    if jax.tree_util.tree_leaves(meta):
        raise ValueError(
            f"{type(codec).__name__} carries decoder state in meta; "
            "quantized ring/tree stages forward payloads only "
            "(meta must be empty)"
        )
    return payload
