"""Overlapped communication runtime: the bucketed, pipelined Channel.

The trainer's wall-clock problem is SERIALIZATION, not just payload
size: ``MeshChannel.reduce_mean`` hands the whole gradient tree to one
collective call, so the first ring hop waits for the full backward pass
and every leaf's ring waits for the previous leaf's.  This module splits
the tree into wire-sized units and pipelines them:

  ``plan_buckets``   flattens the worker-stacked tree into fixed
        byte-budget buckets in REVERSE-layer order (gradients arrive
        last-layer-first during backward, so bucket 0 — the tail of the
        tree — is ready while earlier layers are still differentiating;
        the reverse order is what makes compute/comm overlap possible at
        all).  Buckets group whole leaves: concatenating leaf data would
        move quantization chunk boundaries and silently change the wire
        format — grouping keeps every leaf's payload bit-identical to
        the unbucketed channel, which is the contract below.
  ``AsyncChannel``   a ``Channel`` whose aggregation is issued bucket by
        bucket through explicit ``reduce_start`` / ``finish`` handles.
        ``push_mean`` interleaves the pipeline: bucket i's reduction is
        issued BEFORE bucket i+1's encode, and consecutive buckets share
        no data dependency.  Under ``jit`` the handles delimit
        independent collective computations (one shard_map per bucket
        instead of one for the whole tree) — exactly the freedom XLA's
        latency-hiding scheduler needs to run ring hops concurrently
        with encode and backward compute.

THE CONTRACT (tested): drained synchronously, ``AsyncChannel`` is
bit-exact with ``MeshChannel`` in the same aggregation mode.  Per-leaf
keys are folded from GLOBAL tree positions (``leaf_indices``), so a
bucket subtree reduces to exactly the arrays the full-tree call
produces, in any bucket partition, in any finish order.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm.channel import AGGREGATION_MODES, Channel
from repro.comm.wire import encode_decode_workers, leaf_key
from repro.obs.trace import span

tmap = jax.tree_util.tree_map

#: default per-bucket budget in UNCOMPRESSED per-worker message bytes
#: (inner numel x dense dtype width — the codec's wire payload is
#: smaller, e.g. ~4x for int8): 4 MiB, ~ PyTorch DDP's 25 MB default
#: scaled to the compressed-wire regime
DEFAULT_BUCKET_BYTES = 4 << 20


@dataclass(frozen=True)
class Bucket:
    """One pipeline unit: GLOBAL leaf positions (reverse-layer order)
    plus the per-worker message bytes they carry."""

    indices: Tuple[int, ...]
    nbytes: int


@dataclass(frozen=True)
class BucketPlan:
    buckets: Tuple[Bucket, ...]
    n_leaves: int

    def __len__(self) -> int:
        return len(self.buckets)


def plan_buckets(wtree, bucket_bytes: int = DEFAULT_BUCKET_BYTES, *,
                 per_leaf: bool = False) -> BucketPlan:
    """Partition a worker-stacked pytree into reverse-layer buckets.

    Walks leaves LAST first, accumulating per-worker message bytes
    (inner numel x dtype width — the uplink unit), and closes a bucket
    when adding the next leaf would exceed ``bucket_bytes``.  A single
    leaf above the budget gets its own bucket (leaves are never split —
    see the module docstring).  Works on concrete arrays and
    ``ShapeDtypeStruct`` trees alike, so plans can be built AOT.

    ``per_leaf=True`` ignores the byte budget and emits ONE bucket per
    leaf (still reverse-layer order): the fused-VJP schedule, where each
    layer's message is already encoded the moment backprop produces its
    cotangent, so the natural pipeline unit is the layer itself.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    leaves = jax.tree_util.tree_leaves(wtree)
    buckets: List[Bucket] = []
    cur: List[int] = []
    cur_bytes = 0
    for i in reversed(range(len(leaves))):
        leaf = leaves[i]
        n_inner = 1
        for s in leaf.shape[1:]:
            n_inner *= s
        b = n_inner * np.dtype(leaf.dtype).itemsize
        if per_leaf:
            buckets.append(Bucket((i,), b))
            continue
        if cur and cur_bytes + b > bucket_bytes:
            buckets.append(Bucket(tuple(cur), cur_bytes))
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += b
    if cur:
        buckets.append(Bucket(tuple(cur), cur_bytes))
    return BucketPlan(tuple(buckets), len(leaves))


class Handle(NamedTuple):
    """An in-flight bucket reduction: ``values`` are the issued (traced)
    per-leaf results, ``bucket`` says where they land in the tree."""

    bucket: Bucket
    values: Tuple[Any, ...]


class Inflight(NamedTuple):
    """Everything ``reduce_start`` issued; pass to ``finish`` to drain.
    Handles may also be consumed individually, in any order."""

    treedef: Any
    n_leaves: int
    handles: Tuple[Handle, ...]


@dataclass(frozen=True, eq=False)
class AsyncChannel(Channel):
    """Bucketed overlapped Channel (see module docstring).

    ``mode`` is an aggregation wire format (``AGGREGATION_MODES``);
    ``bucket_bytes`` is the per-bucket budget in UNCOMPRESSED per-worker
    message bytes (see ``plan_buckets``).
    """

    mode: str = "q8_ring_fused"
    mesh: Any = None
    randk_q: float = 0.05
    wspecs: Any = None
    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    q8_block_rows: Optional[int] = None  # fused-q8 scale block (None=default)
    per_leaf: bool = False               # one bucket per leaf (the fused-VJP
    #                                      schedule: payloads arrive layer by
    #                                      layer during backprop)
    obs: Any = None                      # optional StampRecorder: stamps the
    #                                      reduce_start/finish call windows
    #                                      (host side only; no effect on the
    #                                      traced computation)

    def __post_init__(self):
        if self.mode not in AGGREGATION_MODES:
            raise ValueError(
                f"unknown aggregation mode {self.mode!r}; "
                f"have {AGGREGATION_MODES}"
            )
        if self.bucket_bytes <= 0:
            raise ValueError(
                f"bucket_bytes must be positive, got {self.bucket_bytes}"
            )

    # -- plumbing ----------------------------------------------------------

    def _plan(self, wtree) -> BucketPlan:
        return plan_buckets(wtree, self.bucket_bytes, per_leaf=self.per_leaf)

    def _spec_leaves(self, wtree) -> Optional[list]:
        """Worker-stacked PartitionSpecs flattened in leaf order (specs
        are tuple subclasses, so pair against the VALUE tree first)."""
        if self.wspecs is None:
            return None
        paired = tmap(lambda _, sp: sp, wtree, self.wspecs)
        return jax.tree_util.tree_leaves(
            paired, is_leaf=lambda x: isinstance(x, P)
        )

    def _reduce_bucket(self, key, leaves, bucket: Bucket,
                       spec_leaves) -> Handle:
        from repro.dist.collectives import compressed_tree_mean

        sub = [leaves[i] for i in bucket.indices]
        sub_specs = (
            [spec_leaves[i] for i in bucket.indices] if spec_leaves else None
        )
        outs = compressed_tree_mean(
            sub, self.mode, key, self.mesh,
            randk_q=self.randk_q, wspecs=sub_specs,
            leaf_indices=bucket.indices,
            q8_block_rows=self.q8_block_rows,
        )
        return Handle(bucket, tuple(outs))

    def _uplink_bucket(self, q, key, leaves, bucket: Bucket):
        """Encode+decode one bucket's leaves (keys folded from GLOBAL
        leaf positions — bit-exact with the unbucketed uplink)."""
        decoded, bits = [], []
        for i in bucket.indices:
            payload, dec = encode_decode_workers(
                q, leaf_key(key, i), leaves[i]
            )
            decoded.append(dec)
            bits.append(q.wire_bits(payload))
        return decoded, bits

    # -- explicit start/finish API ----------------------------------------

    def reduce_start(self, key, wtree) -> Inflight:
        """Issue every bucket's aggregation; returns handles without
        assembling the tree (callers overlap other work, then
        ``finish``).  With an ``obs`` StampRecorder attached the call
        window is stamped ``"reduce_start"`` — the measured-overlap
        probe (``repro.tune.measure.measure_overlap_hide``) reads these
        stamps off the SAME handles the runtime schedules."""
        if self.obs is not None:
            with self.obs.stamp("reduce_start"):
                return self._reduce_start(key, wtree)
        return self._reduce_start(key, wtree)

    def _reduce_start(self, key, wtree) -> Inflight:
        leaves, treedef = jax.tree_util.tree_flatten(wtree)
        spec_leaves = self._spec_leaves(wtree)
        plan = self._plan(wtree)
        handles = tuple(
            self._reduce_bucket(key, leaves, b, spec_leaves)
            for b in plan.buckets
        )
        return Inflight(treedef, plan.n_leaves, handles)

    def finish(self, inflight: Inflight):
        """Drain all handles back into the aggregated tree (the call
        window is stamped ``"finish"`` when ``obs`` is attached)."""
        if self.obs is not None:
            with self.obs.stamp("finish"):
                return self._finish(inflight)
        return self._finish(inflight)

    def _finish(self, inflight: Inflight):
        out: list = [None] * inflight.n_leaves
        seen = 0
        for h in inflight.handles:
            for j, i in enumerate(h.bucket.indices):
                out[i] = h.values[j]
                seen += 1
        if seen != inflight.n_leaves or any(o is None for o in out):
            raise ValueError(
                f"finish: handles cover {seen} of {inflight.n_leaves} leaves"
            )
        return jax.tree_util.tree_unflatten(inflight.treedef, out)

    # -- Channel interface -------------------------------------------------
    # uplink is inherited: encoding alone has no reductions to overlap
    # with, so bucket order would be a no-op there — only push_mean
    # interleaves (and its per-bucket encodes stay bit-exact with the
    # inherited uplink because keys fold global leaf positions).

    def reduce_mean(self, key, wtree):
        """The synchronous drain: start everything, finish everything —
        bit-exact with ``MeshChannel(mode=...)`` (the contract test)."""
        return self.finish(self.reduce_start(key, wtree))

    def shift_round(self, rule, q, key, wgrads, h, h_bar):
        """The overlapped SHIFT-RULE round: bucket i's message is formed
        (``rule.message_leaf`` with keys folded from GLOBAL leaf
        positions) and its reduction issued BEFORE bucket i+1's message
        — the same interleave as ``push_mean``, but for any rule of the
        phased protocol, so shifted modes (DIANA, EF21, EF-BV, ...) ride
        the overlap runtime instead of being silently serialized.

        Scheduling only: drained synchronously this is bit-exact with
        the default ``Channel.shift_round`` over this channel's
        ``reduce_mean`` (the engine contract test), because both fold
        the same global leaf positions into the message and reduction
        keys.  ``rule.apply`` — the math — is untouched.
        """
        k_msg, k_aux, k_agg = jax.random.split(key, 3)
        g_leaves, treedef = jax.tree_util.tree_flatten(wgrads)
        n = len(g_leaves)
        h_leaves = [None] * n if h is None else jax.tree_util.tree_leaves(h)
        plan = self._plan(wgrads)
        spec_leaves = self._spec_leaves(wgrads)
        msgs: list = [None] * n
        reduced: list = [None] * n
        bits = jnp.zeros((), jnp.float32)

        for bi, b in enumerate(plan.buckets):
            for i in b.indices:
                with span("message"):
                    m, bl = rule.message_leaf(
                        q, leaf_key(k_msg, i), g_leaves[i], h_leaves[i]
                    )
                    bits = bits + bl
                msgs[i] = m
            with span("exchange"), span(f"bucket_{bi}"):
                hd = self._reduce_bucket(k_agg, msgs, b, spec_leaves)
            for j, i in enumerate(hd.bucket.indices):
                reduced[i] = hd.values[j]

        m_tree = jax.tree_util.tree_unflatten(treedef, msgs)
        m_bar = jax.tree_util.tree_unflatten(treedef, reduced)
        with span("shift"):
            aux, extra = rule.aux(k_aux, wgrads, h)
            g_bar, h_new, hb_new = rule.apply(wgrads, m_tree, m_bar, h,
                                              h_bar, aux)
        return g_bar, h_new, hb_new, bits + extra

    def fused_round(self, rule, q, key, msgs, h, h_bar):
        """``shift_round`` for PRE-ENCODED messages (the fused-VJP path:
        backprop already emitted each leaf's decoded message as its
        cotangent, so there is no message phase here — only the
        bucket-by-bucket reductions).  With ``per_leaf=True`` (the
        ``q8_ring_fused_vjp`` channel) every leaf is its own pipeline
        unit, matching the layer-by-layer arrival order of the fused
        backward.

        Bit-exact with ``shift_round`` on the same round key: the
        message keys were pre-derived from this key's ``k_msg`` split
        (``fused_vjp.round_message_keys``), the reductions fold the
        same GLOBAL leaf indices, and the structural per-leaf bits are
        accumulated in the same reverse-layer order.
        """
        from repro.comm.fused_vjp import check_fusible

        check_fusible(rule)
        _k_msg, k_aux, k_agg = jax.random.split(key, 3)
        leaves, treedef = jax.tree_util.tree_flatten(msgs)
        plan = self._plan(msgs)
        spec_leaves = self._spec_leaves(msgs)
        reduced: list = [None] * len(leaves)
        bits = jnp.zeros((), jnp.float32)

        for bi, b in enumerate(plan.buckets):
            for i in b.indices:
                bits = bits + rule.message_bits_aot(q, leaves[i])
            with span("exchange"), span(f"bucket_{bi}"):
                hd = self._reduce_bucket(k_agg, leaves, b, spec_leaves)
            for j, i in enumerate(hd.bucket.indices):
                reduced[i] = hd.values[j]

        m_bar = jax.tree_util.tree_unflatten(treedef, reduced)
        with span("shift"):
            aux, extra = rule.aux(k_aux, msgs, h)
            g_bar, h_new, hb_new = rule.apply(msgs, msgs, m_bar, h, h_bar,
                                              aux)
        return g_bar, h_new, hb_new, bits + extra

    def push_mean(self, q, key, wtree):
        """The overlapped round: each bucket's reduction is issued right
        after its encode and BEFORE the next bucket's encode
        (reverse-layer order) — consecutive buckets share no data
        dependency, so under jit the tail buckets' ring hops can run
        while XLA still has later encodes (and, in the full train step,
        earlier backward) to schedule."""
        k1, k2 = jax.random.split(key)
        leaves, treedef = jax.tree_util.tree_flatten(wtree)
        plan = self._plan(wtree)
        spec_leaves = self._spec_leaves(wtree)
        msgs: list = [None] * len(leaves)
        reduced: list = [None] * len(leaves)
        bits_by_leaf: list = [None] * len(leaves)

        for b in plan.buckets:
            decoded, bits = self._uplink_bucket(q, k1, leaves, b)
            for j, i in enumerate(b.indices):
                msgs[i] = decoded[j]
                bits_by_leaf[i] = bits[j]
            h = self._reduce_bucket(k2, msgs, b, spec_leaves)
            for j, i in enumerate(h.bucket.indices):
                reduced[i] = h.values[j]

        total = jnp.zeros((), jnp.float32)
        for b_leaf in bits_by_leaf:
            total = total + b_leaf
        return (
            jax.tree_util.tree_unflatten(treedef, msgs),
            jax.tree_util.tree_unflatten(treedef, reduced),
            total,
        )
