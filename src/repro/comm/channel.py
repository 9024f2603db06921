"""The Channel: one transport abstraction for compressed messages.

Algorithm 1 has two communication directions, and the framework now
routes BOTH through a single interface instead of ad-hoc call sites:

  ``uplink(q, key, wtree)``
        workers encode their (shifted) gradients with codec ``q`` and
        send the payloads to the master.  Returns the decoded
        worker-stacked messages plus the TOTAL wire bits, computed
        structurally from the actual payloads (``q.wire_bits``) — no
        analytic ``bits(d)`` formulas on any live path.
  ``reduce_mean(key, wtree)``
        master-side aggregation of (already decoded) worker messages in
        the channel's aggregation wire format.
  ``push_mean(q, key, wtree)``
        the composed round: uplink then aggregate.
  ``broadcast(q, key, tree)``
        the downlink (model-broadcast) direction: one encoded message
        from the master, decoded by every worker.

Three interchangeable implementations:

  ``SimChannel``   the vmapped parameter-server of ``core.simulate`` /
        ``core.shift_rules``: the master receives every decoded message
        exactly (aggregation = exact mean over the worker axis).
  ``MeshChannel``  the production path: uplink is identical (messages
        live on their worker's device slice), aggregation wraps
        ``dist.collectives`` — dense psum, shared-pattern Rand-K, or the
        int8 ring/tree all-reduce, all driven by the same codecs.
  ``AsyncChannel`` (``repro.comm.overlap``) the overlapped runtime:
        reverse-layer byte-budget buckets with explicit start/finish
        handles and an interleaved encode/reduce pipeline; drained
        synchronously it is bit-exact with ``MeshChannel``.

``make_channel`` builds the right one from a ``CompressionConfig`` (or a
comm-mode string), replacing the string dispatch that used to live in
``launch/train.py``.  The ``ef21``/``efbv`` comm modes aggregate
densely — the messages themselves are the contractive-compressed
error-feedback increments — and the overlap modes (``q8_ring_overlap``,
``efbv_overlap``) select the AsyncChannel over the Pallas-fused
``q8_ring_fused`` aggregation format.

``Channel.shift_round`` is the engine entry: one shift-rule round
(message -> aux -> reduce -> apply) scheduled by the channel.  All
three channels run the SAME rule algebra (``repro.core.shift_rules``);
the AsyncChannel merely re-schedules it bucket by bucket.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm.wire import encode_decode_workers, encode_meta_free, leaf_key
from repro.obs.trace import span

if TYPE_CHECKING:  # import cycle: core.shift_rules routes through Channel
    from repro.core.compressors import Compressor

tmap = jax.tree_util.tree_map

#: aggregation formats a MeshChannel supports (ef21/efbv/disabled map to
#: dense)
AGGREGATION_MODES = ("dense", "randk_shared", "q8_ring", "q8_ring_fused")

#: every comm-mode string make_channel accepts (config/CLI surface):
#: aggregation formats plus the channel-selecting aliases
CHANNEL_MODES = AGGREGATION_MODES + (
    "sim", "ef21", "efbv", "q8_ring_overlap", "efbv_overlap"
)

#: comm modes served by the bucketed overlapped AsyncChannel
OVERLAP_MODES = ("q8_ring_overlap", "efbv_overlap")

#: comm modes whose wire messages are emitted by the backward pass
#: itself (``repro.comm.fused_vjp``): the AsyncChannel consumes the
#: pre-encoded per-leaf messages with NO standalone encode stage, one
#: bucket per leaf (true per-layer granularity)
FUSED_VJP_MODES = ("q8_ring_fused_vjp",)

CHANNEL_MODES = CHANNEL_MODES + FUSED_VJP_MODES


class Channel:
    """Transport for compressed messages between workers and master."""

    def uplink(self, q: Compressor, key: jax.Array, wtree) -> Tuple[Any, jax.Array]:
        """Encode+decode each worker's slice of a W-stacked pytree.

        Workers get decorrelated keys unless the codec declares a shared
        pattern (correlated Rand-K) or is deterministic, in which case
        every worker samples the same key — the property the
        payload-shrinking collective relies on.  Returns
        ``(decoded W-stacked messages, total wire bits)``; bits are
        structural (summed ``q.wire_bits`` over the actual payloads).
        """
        leaves, treedef = jax.tree_util.tree_flatten(wtree)
        out = []
        bits = jnp.zeros((), jnp.float32)
        for i, leaf in enumerate(leaves):
            lk = leaf_key(key, i)
            payload, decoded = encode_decode_workers(q, lk, leaf)
            bits = bits + q.wire_bits(payload)
            out.append(decoded)
        return jax.tree_util.tree_unflatten(treedef, out), bits

    def reduce_mean(self, key: jax.Array, wtree):
        raise NotImplementedError

    def push_mean(self, q: Compressor, key: jax.Array, wtree):
        """One uplink round: ``(messages, mean over workers, wire bits)``."""
        k1, k2 = jax.random.split(key)
        m, bits = self.uplink(q, k1, wtree)
        return m, self.reduce_mean(k2, m), bits

    def shift_round(self, rule, q: Compressor, key: jax.Array,
                    wgrads, h, h_bar):
        """One shift-rule round, scheduled by this channel.

        The DEFAULT schedule: the rule's whole-tree message, its
        tree-level aux draw, ONE aggregation of the message tree, then
        the rule's ``apply``.  Subclasses that pipeline (the bucketed
        ``AsyncChannel``) override the SCHEDULE only — the per-leaf key
        folding (global tree positions) keeps any re-schedule bit-exact
        with this one.  The phases run under the name scopes
        ``message``, ``exchange`` and ``shift`` (``repro.obs.span``: pure
        trace metadata) — every schedule uses the same three.  Returns
        ``(g_bar, h_new, h_bar_new, bits)``.
        """
        k_msg, k_aux, k_agg = jax.random.split(key, 3)
        with span("message"):
            m, bits = rule.message(q, k_msg, wgrads, h)
        with span("shift"):
            aux, extra = rule.aux(k_aux, wgrads, h)
        with span("exchange"):
            m_bar = self.reduce_mean(k_agg, m)
        with span("shift"):
            g_bar, h_new, hb_new = rule.apply(wgrads, m, m_bar, h, h_bar,
                                              aux)
        return g_bar, h_new, hb_new, bits + extra

    def fused_round(self, rule, q: Compressor, key: jax.Array,
                    msgs, h, h_bar):
        """The shift-round tail for PRE-ENCODED messages.

        ``msgs`` is the already decoded W-stacked message tree the
        fused-backward path emitted as cotangents
        (``repro.comm.fused_vjp``: the keys were derived from THIS
        round key's ``k_msg`` split, so ``k_msg`` is consumed here by
        discarding it).  The schedule is ``shift_round`` minus its
        message phase: aux draw, one aggregation, ``apply`` — with the
        rule's ``msgs`` standing in for the dense gradients its
        fusibility contract says it never reads.  Bits are the per-leaf
        STRUCTURAL ``message_bits_aot``, accumulated in the same leaf
        order as ``rule.message`` so the counter matches the post-hoc
        round bitwise.  Returns ``(g_bar, h_new, h_bar_new, bits)``.
        """
        from repro.comm.fused_vjp import check_fusible

        check_fusible(rule)
        _k_msg, k_aux, k_agg = jax.random.split(key, 3)
        bits = jnp.zeros((), jnp.float32)
        for leaf in jax.tree_util.tree_leaves(msgs):
            bits = bits + rule.message_bits_aot(q, leaf)
        with span("shift"):
            aux, extra = rule.aux(k_aux, msgs, h)
        with span("exchange"):
            m_bar = self.reduce_mean(k_agg, msgs)
        with span("shift"):
            g_bar, h_new, hb_new = rule.apply(msgs, msgs, m_bar, h, h_bar,
                                              aux)
        return g_bar, h_new, hb_new, bits + extra

    def all_to_all(self, q: Compressor, key: jax.Array, x: jax.Array):
        """Forwarded-payload transport for the non-allreduce wires
        (MoE dispatch/combine, pipeline-boundary activations).

        Encodes ``x`` with codec ``q`` and returns the receiver-side
        decode.  The receiver sees ONLY the payload — meta-carrying
        codecs are rejected (``encode_meta_free``), the same contract as
        the quantized ring hops.  Under GSPMD the surrounding dispatch
        einsums lower to the actual all-to-all; what this method pins is
        that the tensor crossing it is the codec's wire format.  Shared
        by all channels (the math is placement-independent); the
        structural accounting for these payloads lives on the ``Wire``
        (``repro.comm.transport``), not here.
        """
        payload = encode_meta_free(q, key, x)
        return q.decode(payload, {}, jax.ShapeDtypeStruct(x.shape, x.dtype))

    def broadcast(self, q: Compressor, key: jax.Array, tree) -> Tuple[Any, jax.Array]:
        """Downlink (model-broadcast): one encoded message per leaf."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out = []
        bits = jnp.zeros((), jnp.float32)
        for i, leaf in enumerate(leaves):
            lk = leaf_key(key, i)
            payload, meta = q.encode(lk, leaf)
            bits = bits + q.wire_bits(payload)
            out.append(
                q.decode(payload, meta,
                         jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
            )
        return jax.tree_util.tree_unflatten(treedef, out), bits


@dataclass(frozen=True, eq=False)
class SimChannel(Channel):
    """Vmapped parameter server: the master sees every decoded message
    exactly, so aggregation is the exact mean over the worker axis."""

    def reduce_mean(self, key, wtree):
        return tmap(lambda a: jnp.mean(a, axis=0), wtree)


@dataclass(frozen=True, eq=False)
class MeshChannel(Channel):
    """Production channel on a device mesh.

    ``mode`` picks the aggregation wire format (see ``AGGREGATION_MODES``);
    ``wspecs`` optionally carries worker-stacked PartitionSpecs so the
    q8 ring's shard_map preserves inner-dim sharding.
    """

    mode: str = "dense"
    mesh: Any = None
    randk_q: float = 0.05
    wspecs: Any = None
    q8_block_rows: Optional[int] = None  # fused-q8 scale block (None=default)

    def __post_init__(self):
        if self.mode not in AGGREGATION_MODES:
            raise ValueError(
                f"unknown aggregation mode {self.mode!r}; "
                f"have {AGGREGATION_MODES}"
            )

    def reduce_mean(self, key, wtree):
        from repro.dist.collectives import compressed_tree_mean

        return compressed_tree_mean(
            wtree, self.mode, key, self.mesh,
            randk_q=self.randk_q, wspecs=self.wspecs,
            q8_block_rows=self.q8_block_rows,
        )


def aggregation_mode_of(mode_or_cfg) -> str:
    """Normalize a comm-mode string / CompressionConfig to an aggregation
    format: disabled configs and the ``ef21``/``efbv`` modes aggregate
    densely (their wire savings are in the per-worker contractive
    messages); the overlap modes aggregate in the Pallas-fused
    ``q8_ring_fused`` wire format."""
    if hasattr(mode_or_cfg, "aggregation_mode"):  # CompressionConfig
        return mode_or_cfg.aggregation_mode
    if mode_or_cfg in ("ef21", "efbv"):
        return "dense"
    if mode_or_cfg in OVERLAP_MODES + FUSED_VJP_MODES:
        return "q8_ring_fused"
    return mode_or_cfg


def make_channel(mode_or_cfg="dense", mesh=None, *, randk_q: float = 0.05,
                 wspecs=None, bucket_bytes: Optional[int] = None,
                 q8_block_rows: Optional[int] = None) -> Channel:
    """Build a Channel from a comm-mode string or a CompressionConfig.

    ``"sim"`` gives the parameter-server SimChannel; the overlap modes
    (``q8_ring_overlap``, ``efbv_overlap``) the bucketed AsyncChannel
    over the fused q8 ring (``bucket_bytes`` sets its per-bucket budget
    in uncompressed per-worker message bytes, and is rejected for every
    other mode); ``q8_ring_fused_vjp`` the same AsyncChannel in per-leaf
    bucket mode, consuming messages the backward pass itself emitted
    (``repro.comm.fused_vjp`` — no standalone encode stage); everything
    else a MeshChannel in the corresponding aggregation format.  Unknown modes raise, naming every accepted
    mode — a typo'd mode must fail HERE, not as a confusing shape/key
    error deep in a collective.
    """
    comm_mode = getattr(mode_or_cfg, "comm_mode", mode_or_cfg)
    if comm_mode == "auto":
        if getattr(mode_or_cfg, "enabled", True):
            raise ValueError(
                "comm_mode 'auto' is a tuner sentinel, not a transport: "
                "resolve it to a concrete mode first (repro.tune.autotune "
                "+ apply_plan, or `train.py --comm_mode auto` which does "
                "both)"
            )
        # a DISABLED config never resolves: its transport is the dense
        # mean, exactly as CompressionConfig.aggregation_mode reports
        comm_mode = "dense"
    if isinstance(comm_mode, str) and comm_mode not in CHANNEL_MODES:
        raise ValueError(
            f"unknown comm mode {comm_mode!r}; have channel modes "
            f"{CHANNEL_MODES} (aggregation formats: {AGGREGATION_MODES})"
        )
    if (bucket_bytes is not None
            and comm_mode not in OVERLAP_MODES + FUSED_VJP_MODES):
        raise ValueError(
            f"bucket_bytes only applies to the overlap channels "
            f"{OVERLAP_MODES + FUSED_VJP_MODES}, not {comm_mode!r} (it "
            f"would be silently ignored)"
        )
    if comm_mode == "sim":  # uniform: string or config comm_mode
        return SimChannel()
    if hasattr(mode_or_cfg, "comm_mode"):
        randk_q = mode_or_cfg.randk_q
        if bucket_bytes is None:
            bucket_bytes = getattr(mode_or_cfg, "overlap_bucket_bytes", None)
        if q8_block_rows is None:
            q8_block_rows = getattr(mode_or_cfg, "q8_block_rows", None)
    mode = aggregation_mode_of(mode_or_cfg)
    if comm_mode in OVERLAP_MODES + FUSED_VJP_MODES:
        from repro.comm.overlap import DEFAULT_BUCKET_BYTES, AsyncChannel

        return AsyncChannel(
            mode=mode, mesh=mesh, randk_q=randk_q, wspecs=wspecs,
            bucket_bytes=(DEFAULT_BUCKET_BYTES if bucket_bytes is None
                          else bucket_bytes),
            q8_block_rows=q8_block_rows,
            # fused-VJP: payloads arrive leaf by leaf during backprop,
            # so the plan is one bucket per leaf (per-layer granularity)
            per_leaf=comm_mode in FUSED_VJP_MODES,
        )
    return MeshChannel(mode=mode, mesh=mesh, randk_q=randk_q, wspecs=wspecs,
                       q8_block_rows=q8_block_rows)


def resync_h_bar(h, h_bar, step, every: int):
    """Bound the shift-tracking drift of lossy aggregation.

    Stateful rules track the master shift INCREMENTALLY
    (``h_bar += eta * m_bar``), so lossy aggregation formats
    (``randk_shared``, the q8 rings) make ``h_bar - mean_i h_i`` a
    zero-mean random walk of the per-step aggregation noise (see the
    ARCHITECTURE.md "Algorithm layer" footnote).  Every ``every`` rounds
    — on steps where ``step % every == every - 1`` — this replaces
    ``h_bar`` with the DENSE reduce (exact worker mean) of the current
    shifts, resetting the walk to zero at the cost of one uncompressed
    collective per window.  ``every <= 0`` (the config default) and
    stateless rules (``h``/``h_bar`` None) are no-ops; ``lax.cond``
    keeps the dense reduce off the non-firing steps.
    """
    if every <= 0 or h is None or h_bar is None:
        return h_bar
    from repro.dist.collectives import dense_mean

    fire = (step % every) == (every - 1)
    return jax.lax.cond(fire, lambda: dense_mean(h), lambda: h_bar)


def collective_payload_scale(cfg, d_nominal: int = 1_000_000) -> dict:
    """Per-collective-kind wire fraction for the HLO payload cost model.

    Only aggregation formats whose HLO lowering is DENSE while the
    protocol payload is compressed need a scale.  The codec-driven
    collectives are structurally honest on their own: the q8 ring's s8
    payloads and the shared-pattern Rand-K's K-sized value mean both
    appear at true wire size in the HLO text (scale 1 — the ROADMAP's
    "wire randk_shared payload accounting into the HLO cost model" item
    is satisfied by the lowering itself).  EF21 is the remaining dense
    lowering: its aggregation is an exact mean of DECODED sparse
    messages, so the all-reduce is full-width in HLO while the wire
    carries the contractive codec's payload — scale by that codec's
    wire fraction, derived structurally (``aot_wire_bits``), not from an
    analytic formula.  The same holds for ``efbv`` (EF-BV shares EF21's
    dense aggregation of decoded messages).  Apply it to the
    GRADIENT-MESSAGE share only
    (``hlo_cost.apply_gradient_payload_model``): activation all-reduces
    under model parallelism are genuine dense traffic.
    """
    if not getattr(cfg, "enabled", True):
        return {}
    if getattr(cfg, "comm_mode", "dense") in ("ef21", "efbv"):
        from repro.core.compressors import aot_wire_bits, make_compressor

        q = make_compressor(cfg.compressor, **dict(cfg.compressor_kwargs))
        return {"all-reduce": aot_wire_bits(q, d_nominal) / (32.0 * d_nominal)}
    return {}
