"""Distributed training step with first-class shifted compression.

This is Algorithm 1 (DCGD-SHIFT) mapped onto the TPU mesh:

  * "worker i" = one (pod, data) slice; per-worker gradients come from a
    vmap over the worker axis (``dist.worker_grads``), sharded
    P(("pod","data"), ...).
  * ALL algorithm math lives in the ONE phased rule engine
    (``repro.core.shift_rules`` for the gradient direction,
    ``repro.core.iterate_comp.VRGDCI`` for compressed iterates): the
    step below only plumbs ``TrainState`` fields through
    ``rule.round(...)``.  There is NO per-rule update math in this
    module — a rule lands once in ``repro.core`` and runs everywhere
    (reference simulator, this mesh step, the overlap runtime), which
    the cross-layer bit-exactness tests in ``tests/test_shift_engine.py``
    pin.
  * ALL communication goes through one ``repro.comm.Channel``
    (``MeshChannel`` here, ``AsyncChannel`` for the overlap modes):
    wire bits are accounted STRUCTURALLY from the actual payloads and
    aggregation runs in the configured wire format (dense psum /
    shared-pattern Rand-K / int8 ring) — no comm-mode string dispatch
    lives here either.
  * The master's aggregated shift h^k is tracked INCREMENTALLY by the
    rules (Alg. 1 line 14 as the paper notes: h^{k+1} = h^k + alpha*m^k
    for DIANA) so no uncompressed collective ever materializes for it.

CLI:  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
          [--comm_mode dense|randk_shared|q8_ring|q8_ring_overlap|ef21|\
           efbv|efbv_overlap|q8_ring_fused_vjp|auto] [--autotune] \
          [--tune_plan PLAN.json] ...

``--comm_mode auto`` resolves through ``repro.tune``: fingerprint the
(model x mesh x world-size x compressor) workload, reuse the cached
``TunePlan`` on a hit, otherwise calibrate an alpha-beta link model by
timed micro-reduces of the real leaf shapes, rank every candidate plan
by predicted step time, verify the top few by measurement, and persist
the winner (strict JSON under ``--tune_cache``).  ``--autotune`` forces
a fresh search even on a hit; ``--tune_plan`` applies an explicit plan
file; ``--tune_modes`` restricts the candidate grid (CI keeps measured
candidates tiny — interpret-mode Pallas is slow on CPU).

``q8_ring_overlap`` / ``efbv_overlap`` route the round through
``comm.AsyncChannel``: reverse-layer byte-budget buckets over the
Pallas-fused int8 ring, each bucket's message formed and its reduction
issued before the next bucket's message (``AsyncChannel.shift_round``),
so XLA can overlap ring hops with encode and backward compute — for
EVERY rule of the engine, shifted ones included.

``q8_ring_fused_vjp`` goes one step further and deletes the standalone
encode stage entirely (``repro.comm.fused_vjp``): every param leaf is
wrapped in an identity ``custom_vjp`` whose backward applies the
rule's ``message_leaf`` shift+encode, so the backward pass EMITS the
decoded wire messages as its cotangents and the AsyncChannel (per-leaf
buckets) only runs the reduce/apply tail — bit-exact with the post-hoc
rounds per shift rule (tests/test_fused_vjp.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import (
    CHANNEL_MODES,
    FUSED_VJP_MODES,
    WIRE_CODEC_FLAGS,
    build_transport,
    make_channel,
    resync_h_bar,
    wire_stream,
)
from repro.configs import get_config, get_smoke_config
from repro.configs.base import CompressionConfig, ModelConfig, TrainConfig
from repro.core import SHIFT_RULES
from repro.core.iterate_comp import VRGDCI
from repro.core.shift_rules import residual_sq_diag
from repro.dist import (
    params_pspecs,
    per_worker_grads,
    split_batch,
    validate_pspecs,
    worker_stacked_pspec,
)
from repro.data.tokens import TokenStream
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, n_workers
from repro.models import model as M
from repro.optim import make_optimizer

tmap = jax.tree_util.tree_map

#: CLI comm modes — DERIVED from the channel registry (minus the
#: reference-only parameter server) so the two surfaces cannot drift
COMM_MODES = tuple(m for m in CHANNEL_MODES if m != "sim")

#: CLI shift rules — the engine registry minus the oracle rule (which
#: needs grads at the optimum) plus the iterate-compression Algorithm 2
SHIFT_RULE_CHOICES = tuple(
    r for r in SHIFT_RULES if r != "star"
) + ("vr_gdci",)


class TrainState(NamedTuple):
    params: Any
    opt: Any
    h: Any            # worker-stacked shifts (None for stateless rules)
    h_bar: Any        # master aggregated shift (params-like; None if zero)
    key: jax.Array
    step: jax.Array
    bits: jax.Array   # cumulative uplink bits (model-size units, f32)


def init_state(key, cfg: ModelConfig, tcfg: TrainConfig, w: int) -> TrainState:
    kp, kk = jax.random.split(key)
    params = M.init_params(kp, cfg)
    opt = make_optimizer(tcfg).init(params)
    comp = tcfg.compression
    if comp.enabled:
        # the rule decides its own state: stateless rules (fixed/dcgd)
        # allocate nothing; stateful ones get worker-stacked shifts in
        # the gradient dtype (bf16 at scale — a full f32 copy per worker
        # would dominate HBM for the 32B archs) plus the master h_bar
        _, rule = comp.make(learning_rate=tcfg.learning_rate)
        wlike = tmap(
            lambda p: jax.ShapeDtypeStruct((w, *p.shape), p.dtype), params
        )
        h = rule.init(wlike)
        h_bar = rule.init_bar(wlike)
    else:
        h = None
        h_bar = None
    return TrainState(params, opt, h, h_bar, kk,
                      jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))


def build_channel(comp: CompressionConfig, cfg: ModelConfig, mesh, w: int):
    """The MeshChannel for this run, with worker-stacked specs when the
    aggregation runs a shard_map (q8 ring / shared Rand-K)."""
    wspecs = None
    if (
        comp.enabled
        and comp.aggregation_mode in ("q8_ring", "q8_ring_fused",
                                      "randk_shared")
        and mesh is not None
    ):
        # worker-stacked grad specs so the ring's shard_map keeps the
        # model-axis sharding of inner dims (no whole-leaf gathers)
        params_shapes = jax.eval_shape(
            lambda k: M.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
        )
        inner = validate_pspecs(params_shapes, params_pspecs(params_shapes), mesh)
        wspecs = tmap(lambda sp: worker_stacked_pspec(mesh, sp), inner,
                      is_leaf=lambda x: isinstance(x, P))
        wshapes = tmap(lambda p: jax.ShapeDtypeStruct((w, *p.shape), p.dtype),
                       params_shapes)
        wspecs = validate_pspecs(wshapes, wspecs, mesh)
    return make_channel(comp, mesh, wspecs=wspecs)


def _tree_dist(a, b) -> jax.Array:
    """Global l2 distance ``||a - b||`` over two pytrees (f32)."""
    sq = jnp.zeros((), jnp.float32)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        d = la.astype(jnp.float32) - lb.astype(jnp.float32)
        sq = sq + jnp.sum(d * d)
    return jnp.sqrt(sq)


def build_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh, w: int,
                     diag: bool = False):
    """Returns train_step(state, batch) -> (state, metrics) — pure, jittable.

    The step is RULE PLUMBING ONLY: per-worker gradients in, one
    ``rule.round`` (the engine: message -> aggregate -> apply, scheduled
    by the channel), optimizer out.  Iterate-compression rules
    (``VRGDCI``) update the params inside their round, so the optimizer
    is bypassed for them — the paper's gradient mapping is plain SGD.

    ``diag=True`` adds shift-rule diagnostics to the METRICS dict only —
    ``h_bar_drift`` (||h_bar - mean_i h_i||, the lossy-aggregation
    tracking error ``resync_h_bar`` bounds) and ``ef_err_norm``
    (||g_bar - mean_i g_i||, the compression error of the round).  The
    returned STATE is bit-exact with ``diag=False`` (pinned in
    tests/test_obs.py): diagnostics consume no randomness and feed
    nothing back.  Phases are annotated with ``repro.obs.span`` — pure
    trace metadata, no runtime ops, no extra compilations.
    """
    from repro.obs import span
    if getattr(tcfg, "train_attn_chunk", 0) and tcfg.train_attn_chunk > 0:
        cfg = cfg.with_(attn_q_chunk=tcfg.train_attn_chunk)
    comp = tcfg.compression
    optimizer = make_optimizer(tcfg)
    channel = build_channel(comp, cfg, mesh, w)
    if comp.enabled:
        q, rule = comp.make(learning_rate=tcfg.learning_rate)
        iterate_rule = isinstance(rule, VRGDCI)
    else:
        q, rule, iterate_rule = None, None, False
    fused = comp.enabled and comp.comm_mode in FUSED_VJP_MODES
    if fused:
        from repro.comm import fused_vjp

        if iterate_rule:
            raise ValueError(
                "comm_mode 'q8_ring_fused_vjp' fuses GRADIENT-message "
                "encode into the backward pass; the iterate-compression "
                "rule 'vr_gdci' has no gradient message to fuse"
            )
        fused_vjp.check_fusible(rule)
    # ALL of this step's traffic is registered on the transport: the
    # grad wire wraps the channel+rule above (bit-exact — Wire passes
    # the round key through verbatim), and any configured moe/act wires
    # ride into the forward pass
    transport = build_transport(comp, cfg, channel, rule=rule, msg_codec=q,
                                w=w)
    grad_wire = transport["grad"]
    wired = ("moe" in transport) or ("act" in transport)

    def loss_fn(params, batch):
        if fused or wired:
            batch = dict(batch)
        tap = None
        if fused:
            # the fused-backward encode: wrap every param leaf so its
            # dense cotangent is replaced by the decoded shifted-
            # compressed message the moment backprop produces it —
            # jax.grad of this loss then EMITS the wire message tree
            # directly, and the dense gradient tree never materializes
            keys = batch.pop("fused_keys")
            fh = batch.pop("fused_h", None)
            tap = lambda p: fused_vjp.encode_on_backward(  # noqa: E731
                rule, q, p, keys, fh
            )
        if wired:
            wire_key = batch.pop("wire_key")
            return M.train_loss(params, cfg, batch, wires=transport,
                                wire_key=wire_key, param_tap=tap)
        return M.train_loss(params, cfg, batch, param_tap=tap)

    def train_step(state: TrainState, batch):
        # the step's mesh is the ambient mesh while it traces: the
        # per-worker maps (``repro.comm.wire.on_workers``) read it
        if mesh is None:
            return _step(state, batch)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _step(state, batch)

    def _step(state: TrainState, batch):
        wbatch = split_batch(batch, w)
        # the round key is split BEFORE the backward pass (the fused
        # path derives its message keys from ``sub``); the split is
        # pure, so every mode's trajectory is bitwise unchanged
        key, sub = jax.random.split(state.key)
        if wired:
            # per-worker wire keys, derived from a stream disjoint from
            # the round key (which stays byte-identical to the unwired
            # step)
            kw = wire_stream(state.key, "transport")
            wbatch = dict(wbatch, wire_key=jax.random.split(kw, w))
        if fused:
            # per-leaf per-worker message keys, pre-derived from the
            # round key exactly as the post-hoc rounds derive them
            # (Channel.shift_round's k_msg split + global leaf fold);
            # every array leaf is (w, ...)-stacked so the tuple rides
            # the worker vmap with the rest of the batch
            wbatch = dict(wbatch, fused_keys=fused_vjp.round_message_keys(
                rule, q, sub, state.params, w
            ))
            if state.h is not None:
                wbatch = dict(wbatch, fused_h=state.h)
        with span("train/grads"):
            grads, loss, metrics = per_worker_grads(
                loss_fn, state.params, wbatch
            )

        extra = {}
        if not comp.enabled:
            with span("train/reduce"):
                g_bar = grad_wire.reduce_mean(sub, grads)
            with span("train/apply"):
                new_params, opt = optimizer.update(
                    g_bar, state.opt, state.params
                )
            h, h_bar, bits = state.h, state.h_bar, state.bits
        elif iterate_rule:
            # Algorithm 2: the round returns the mixed iterate directly
            with span("train/round"):
                new_params, h, h_bar, step_bits = grad_wire.iterate_round(
                    sub, state.params, grads, state.h, state.h_bar
                )
            opt = state.opt
            bits = state.bits + step_bits
        else:
            with span("train/round"):
                if fused:
                    # ``grads`` here ARE the decoded wire messages (the
                    # fused backward emitted them as cotangents): the
                    # round is its reduce/apply tail, no encode stage
                    g_bar, h, h_bar, step_bits = grad_wire.fused_round(
                        sub, grads, state.h, state.h_bar
                    )
                else:
                    g_bar, h, h_bar, step_bits = grad_wire.shift_round(
                        sub, grads, state.h, state.h_bar
                    )
                # bound the shift-tracking drift of lossy aggregation:
                # every N rounds h_bar resyncs to the exact worker mean
                h_bar = resync_h_bar(h, h_bar, state.step,
                                     comp.drift_resync_every)
            with span("train/apply"):
                new_params, opt = optimizer.update(
                    g_bar, state.opt, state.params
                )
            bits = state.bits + step_bits
            if diag:
                if not fused:
                    # fused mode has no dense per-worker gradients to
                    # compare against — that deletion is the point
                    g_mean = tmap(
                        lambda g: jnp.mean(g.astype(jnp.float32), axis=0),
                        grads,
                    )
                    extra["ef_err_norm"] = _tree_dist(g_bar, g_mean)
                    # the paper's headline probe: ||g - h||^2 vs ||g||^2
                    # against the PRE-round shift (what the wire carried)
                    extra.update(residual_sq_diag(grads, state.h))
                if h is not None and h_bar is not None:
                    h_mean = tmap(
                        lambda x: jnp.mean(x.astype(jnp.float32), axis=0), h
                    )
                    extra["h_bar_drift"] = _tree_dist(h_bar, h_mean)

        new_state = TrainState(new_params, opt, h, h_bar, key,
                               state.step + 1, bits)
        return new_state, {**metrics, "loss": loss, "bits": bits, **extra}

    return train_step


# ---------------------------------------------------------------------------
# Sharding assembly for the production mesh
# ---------------------------------------------------------------------------


def state_pspecs(state_shapes, mesh, tcfg: TrainConfig):
    """PartitionSpecs for a TrainState, validated against the mesh."""
    fsdp = tcfg.fsdp_params
    p_specs = params_pspecs(state_shapes.params, fsdp=fsdp)
    p_specs = validate_pspecs(state_shapes.params, p_specs, mesh)
    opt_data = tcfg.zero_opt_state
    m_specs = params_pspecs(state_shapes.opt.m, fsdp=opt_data)
    m_specs = validate_pspecs(state_shapes.opt.m, m_specs, mesh)
    v_specs = params_pspecs(state_shapes.opt.v, fsdp=opt_data)
    v_specs = validate_pspecs(state_shapes.opt.v, v_specs, mesh)

    if state_shapes.h is not None:
        inner = params_pspecs(state_shapes.params, fsdp=False)
        h_specs = tmap(lambda sp: worker_stacked_pspec(mesh, sp), inner,
                       is_leaf=lambda x: isinstance(x, P))
        h_specs = validate_pspecs(state_shapes.h, h_specs, mesh)
        hb_specs = params_pspecs(state_shapes.h_bar, fsdp=True)
        hb_specs = validate_pspecs(state_shapes.h_bar, hb_specs, mesh)
    else:
        h_specs = None
        hb_specs = None

    return TrainState(
        params=p_specs,
        opt=type(state_shapes.opt)(step=P(), m=m_specs, v=v_specs),
        h=h_specs,
        h_bar=hb_specs,
        key=P(),
        step=P(),
        bits=P(),
    )


def batch_pspecs(batch_shapes, mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return tmap(lambda _: P(axes), batch_shapes)


def named_shardings(specs, mesh):
    """A PartitionSpec tree as NamedShardings on ``mesh``."""
    return tmap(lambda sp: NamedSharding(mesh, sp), specs,
                is_leaf=lambda x: isinstance(x, P))


def init_placed_state(key, cfg: ModelConfig, tcfg: TrainConfig, mesh,
                      w: int):
    """``init_state`` built directly in its mesh layout (``state_pspecs``:
    optimizer moments sharded over the data axis, worker shifts one row
    per data slice), so no device ever holds the whole state.  Returns
    ``(state, state_shardings)``."""
    init = lambda k: init_state(k, cfg, tcfg, w)  # noqa: E731
    sh = named_shardings(state_pspecs(jax.eval_shape(init, key), mesh, tcfg),
                         mesh)
    return jax.jit(init, out_shardings=sh)(key), sh


def jit_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh, w: int,
                   state_shardings, *, diag: bool = False):
    """The jitted step: the state is donated (its buffers are reused for
    the new state) and stays in ``state_shardings`` from step to step."""
    return jax.jit(build_train_step(cfg, tcfg, mesh, w, diag=diag),
                   out_shardings=(state_shardings, None), donate_argnums=0)


# ---------------------------------------------------------------------------
# CLI driver (host-scale): trains a reduced/smoke or small full config
# ---------------------------------------------------------------------------


def dense_step_analysis(cfg: ModelConfig, mesh, w: int, lr: float,
                        batch: int, seq: int):
    """Loop-aware HLO cost of THIS run's train step with compression
    disabled — the compute/memory time every tuner candidate shares, so
    the overlap candidates' hide credit is charged against the real
    backward pass (without it, compute_s is 0 and bucketed overlap can
    never beat its own launch overhead).  Returns None (with a warning)
    if the step cannot be lowered here — the search then ranks by comm
    alone, exactly the pre-analysis behavior."""
    from repro.launch import hlo_cost

    try:
        tcfg = TrainConfig(learning_rate=lr,
                           compression=CompressionConfig(enabled=False))
        step = build_train_step(cfg, tcfg, mesh, w)
        state_shapes = jax.eval_shape(
            lambda k: init_state(k, cfg, tcfg, w),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        batch_shapes = tmap(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            TokenStream(cfg, seq, batch).batch(0),
        )
        hlo = jax.jit(step).lower(state_shapes, batch_shapes).compile().as_text()
        return hlo_cost.analyze(hlo)
    except Exception as e:  # noqa: BLE001 — tuning must not kill training
        print(f"tune: WARNING: dense-step HLO analysis failed "
              f"({type(e).__name__}: {e}); ranking candidates by comm time "
              f"only (overlap modes get no compute-hide credit)")
        return None


def resolve_comm_auto(comp: CompressionConfig, cfg: ModelConfig, mesh, w: int,
                      *, plan_path=None, cache_dir=None, force=False,
                      tune_modes=None, lr: float = 3e-4, batch: int = 8,
                      seq: int = 128, obs_sink=None):
    """Resolve ``comm_mode='auto'`` (or an explicit ``--tune_plan`` /
    ``--autotune`` request) via ``repro.tune``, printing what happened —
    the fingerprint, whether the plan came from the cache, and the
    chosen knobs.  Returns ``(resolved CompressionConfig, TunePlan)`` —
    the plan carries the predicted step time the obs layer logs next to
    every measured step.  ``obs_sink`` receives the search's structured
    warning events (e.g. ``omega_unavailable``)."""
    from repro import tune
    from repro.core.compressors import make_compressor

    if plan_path:
        plan = tune.load_plan(plan_path)
        source = f"plan file {plan_path}"
    else:
        params_shapes = jax.eval_shape(
            lambda k: M.init_params(k, cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        modes = (
            tuple(m for m in tune_modes.split(",") if m)
            if tune_modes else None
        )
        wlike = tmap(
            lambda p: jax.ShapeDtypeStruct((w, *p.shape), p.dtype),
            params_shapes,
        )
        codec = make_compressor(comp.compressor,
                                **dict(comp.compressor_kwargs))
        plan, hit = tune.autotune(
            comp, params_shapes, mesh, w,
            cache_dir=(cache_dir or tune.DEFAULT_CACHE_DIR),
            force=force, modes=modes,
            # evaluated LAZILY on a cache miss only: the HLO analysis
            # (one dense-step lower+compile), rate calibration, the
            # MEASURED overlap hide fraction (three timed phases through
            # the real AsyncChannel handles), and the MEASURED compressor
            # variance (obs.quality distortion over the real leaf shapes)
            # replace nominal/analytic constants
            analysis_fn=lambda: dense_step_analysis(
                cfg, mesh, w, lr, batch, seq
            ),
            rates_fn=tune.calibrate_rates,
            hide_fn=lambda: tune.measure_overlap_hide(
                mesh, wlike, cap_bytes=1 << 20, iters=2
            ),
            omega_fn=lambda: (tune.measure_omega(
                codec, wlike, mesh=mesh, cap_bytes=1 << 20, iters=2
            ) if hasattr(codec, "omega") else None),
            obs_sink=obs_sink,
        )
        source = "cache hit" if hit else "searched"
    resolved = tune.apply_plan(comp, plan)
    measured = (f"{plan.measured_step_s:.3e}s"
                if plan.measured_step_s is not None else "n/a")
    hide = (f"{plan.hide_fraction:.2f} ({plan.hide_source})"
            if plan.hide_fraction is not None else plan.hide_source)
    omega = (f"{plan.omega:.3g} ({plan.omega_source})"
             if plan.omega is not None else plan.omega_source)
    print(f"tune: {source}  fingerprint={plan.fingerprint[:12]}  "
          f"-> comm_mode={resolved.comm_mode} "
          f"bucket={resolved.overlap_bucket_bytes} "
          f"randk_q={resolved.randk_q:g} "
          f"q8_block={resolved.q8_block_rows} "
          f"(predicted {plan.predicted_step_s:.3e}s, measured {measured}, "
          f"hide {hide}, omega {omega})")
    return resolved, plan


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of the arch")
    ap.add_argument("--compressor", default="natural")
    ap.add_argument("--shift-rule", "--shift_rule", dest="shift_rule",
                    default="diana", choices=list(SHIFT_RULE_CHOICES))
    ap.add_argument("--comm-mode", "--comm_mode", dest="comm_mode",
                    default="dense", choices=list(COMM_MODES) + ["auto"],
                    help="Channel aggregation format; ef21/efbv select "
                         "the error-feedback modes (implying their rule); "
                         "the *_overlap modes run the bucketed "
                         "AsyncChannel over the Pallas-fused q8 ring; "
                         "q8_ring_fused_vjp fuses the encode into the "
                         "backward pass itself (messages emitted as "
                         "cotangents, per-leaf buckets, no standalone "
                         "encode stage); 'auto' resolves through the "
                         "repro.tune cost-model search (cached by "
                         "fingerprint)")
    ap.add_argument("--autotune", action="store_true",
                    help="force a fresh tune search even when a cached "
                         "plan matches this workload's fingerprint")
    ap.add_argument("--tune-plan", "--tune_plan", dest="tune_plan",
                    default=None,
                    help="apply an explicit TunePlan JSON (skips the "
                         "search and the cache)")
    ap.add_argument("--tune-cache", "--tune_cache", dest="tune_cache",
                    default=None,
                    help="plan-cache directory (default experiments/tune)")
    ap.add_argument("--tune-modes", "--tune_modes", dest="tune_modes",
                    default=None,
                    help="comma-separated subset of tunable comm modes to "
                         "search (keeps measured candidates tiny in CI)")
    ap.add_argument("--moe-wire", "--moe_wire", dest="moe_wire",
                    default="none", choices=list(WIRE_CODEC_FLAGS),
                    help="codec for the MoE dispatch/combine all-to-all "
                         "wire ('none' leaves it off the transport; "
                         "'dense' routes it uncompressed)")
    ap.add_argument("--act-wire", "--act_wire", dest="act_wire",
                    default="none", choices=list(WIRE_CODEC_FLAGS),
                    help="codec for the pipeline-boundary activation "
                         "wire (block-boundary residuals, straight-"
                         "through backward)")
    ap.add_argument("--model-wire", "--model_wire", dest="model_wire",
                    default="none", choices=list(WIRE_CODEC_FLAGS),
                    help="codec for the trainer->serving model-delta "
                         "downlink ('none' leaves it off the transport; "
                         "'dense' is the lossless bit-pattern delta "
                         "stream)")
    ap.add_argument("--publish_every", "--publish-every",
                    dest="publish_every", type=int, default=1,
                    help="trainer steps between model-delta publishes on "
                         "the downlink")
    ap.add_argument("--serve_fleet", "--serve-fleet", dest="serve_fleet",
                    type=int, default=0,
                    help="N > 0: co-run N continuous-batching serving "
                         "replicas off the model-delta stream while "
                         "training")
    ap.add_argument("--stale_k", "--stale-k", dest="stale_k", type=int,
                    default=4,
                    help="fleet staleness bound K (trainer steps behind) "
                         "before a dense resync")
    ap.add_argument("--drift-resync-every", "--drift_resync_every",
                    dest="drift_resync_every", type=int, default=0,
                    help="every N rounds resync h_bar from a dense reduce "
                         "of the worker shifts (bounds shift-tracking "
                         "drift over lossy aggregation; 0 = off)")
    ap.add_argument("--efbv-eta", "--efbv_eta", dest="efbv_eta",
                    type=float, default=1.0,
                    help="EF-BV shift integration rate (1.0 = EF21)")
    ap.add_argument("--efbv-nu", "--efbv_nu", dest="efbv_nu",
                    type=float, default=1.0,
                    help="EF-BV estimator mixing")
    ap.add_argument("--no-compression", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--metrics_out", "--metrics-out", dest="metrics_out",
                    default=None,
                    help="write per-step obs records (strict JSONL, "
                         "rotated) here; enables shift-rule diagnostics "
                         "(h_bar drift, EF error norm) in the metrics "
                         "dict — the returned train STATE stays "
                         "bit-exact with the uninstrumented run")
    ap.add_argument("--trace", action="store_true",
                    help="record host wall-clock spans per phase "
                         "(encode/reduce/apply) and include the span "
                         "table in the run summary")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.smoke:
        # the CPU-sized smoke variant runs in f32; a full config trains
        # in its published dtype (bf16 params, shifts and activations)
        cfg = cfg.with_(dtype="float32")
    comp = CompressionConfig(
        enabled=not args.no_compression,
        compressor=args.compressor,
        shift_rule=args.shift_rule,
        comm_mode=args.comm_mode,
        efbv_eta=args.efbv_eta,
        efbv_nu=args.efbv_nu,
        drift_resync_every=args.drift_resync_every,
        moe_wire=args.moe_wire,
        act_wire=args.act_wire,
        model_wire=args.model_wire,
        publish_every=args.publish_every,
    )
    if args.serve_fleet > 0 and args.model_wire == "none":
        raise SystemExit("--serve_fleet needs a model downlink; pass "
                         "--model_wire (dense/q8/natural/...)")
    mesh = make_host_mesh()
    w = n_workers(mesh)
    if args.batch % w:
        raise SystemExit(f"--batch must be divisible by {w} workers")

    if (args.autotune or args.tune_plan) and args.comm_mode != "auto":
        # an explicit concrete --comm_mode would be SILENTLY replaced by
        # the plan — make overriding it an explicit opt-in
        raise SystemExit(
            "--autotune/--tune_plan replace the communication plan; they "
            "require --comm_mode auto (you passed "
            f"--comm_mode {args.comm_mode})"
        )
    # the sink exists BEFORE plan resolution so the tune search's
    # structured warning events (omega_unavailable) land in --metrics_out
    obs_on = args.metrics_out is not None
    sink = None
    recorder = None
    if obs_on or args.trace:
        from repro import obs

        if obs_on:
            sink = obs.JsonlSink(args.metrics_out)
        if args.trace:
            recorder = obs.SpanRecorder()

    plan = None
    if comp.enabled and comp.comm_mode == "auto":
        comp, plan = resolve_comm_auto(
            comp, cfg, mesh, w,
            plan_path=args.tune_plan, cache_dir=args.tune_cache,
            force=args.autotune, tune_modes=args.tune_modes,
            lr=args.lr, batch=args.batch, seq=args.seq,
            obs_sink=sink,
        )
        # an explicit CLI wire flag beats the plan's (plans searched
        # with the default grids pin both wires to 'none')
        if args.moe_wire != "none":
            comp = dataclasses.replace(comp, moe_wire=args.moe_wire)
        if args.act_wire != "none":
            comp = dataclasses.replace(comp, act_wire=args.act_wire)
        if args.model_wire != "none":
            comp = dataclasses.replace(comp, model_wire=args.model_wire)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(1, args.steps // 10),
                       compression=comp)

    state, state_sh = init_placed_state(jax.random.PRNGKey(0), cfg, tcfg,
                                        mesh, w)
    step_fn = jit_train_step(cfg, tcfg, mesh, w, state_sh, diag=obs_on)
    stream = TokenStream(cfg, args.seq, args.batch)
    batch_sh = named_shardings(batch_pspecs(stream.batch(0), mesh), mesh)

    predicted_step_s = None
    if obs_on:
        from repro import tune
        from repro.comm import SimChannel, build_transport

        # predicted step time for the measured-vs-predicted ledger: the
        # plan's number when the tuner picked the mode, a nominal
        # comm-only prediction otherwise (no analysis lowered — the gap
        # is the point, not a problem)
        if plan is not None:
            predicted_step_s = plan.predicted_step_s
        elif comp.enabled and comp.comm_mode in tune.TUNABLE_MODES:
            params_shapes = jax.eval_shape(
                lambda k: M.init_params(k, cfg),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            )
            wlike = tmap(
                lambda p: jax.ShapeDtypeStruct((w, *p.shape), p.dtype),
                params_shapes,
            )
            cand = tune.Candidate(
                comp.comm_mode,
                bucket_bytes=comp.overlap_bucket_bytes,
                randk_q=comp.randk_q,
                q8_block_rows=comp.q8_block_rows or 64,
                efbv_eta=comp.efbv_eta, efbv_nu=comp.efbv_nu,
                compressor=comp.compressor,
                compressor_kwargs=tuple(comp.compressor_kwargs),
            )
            predicted_step_s = tune.predict_step(
                cand, wlike, tune.LinkModel.nominal(), w
            ).step_s

        # run header: per-wire telemetry (structural bits AND payload
        # bytes, measured codec timings) + the measured overlap hide
        params_shapes = jax.eval_shape(
            lambda k: M.init_params(k, cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        acct = build_transport(
            comp, cfg, SimChannel(), w=w, params_like=params_shapes,
            tokens_per_worker=(args.batch // w) * args.seq,
        )
        wlike = tmap(
            lambda p: jax.ShapeDtypeStruct((w, *p.shape), p.dtype),
            params_shapes,
        )
        if plan is not None and plan.hide_fraction is not None:
            hide_fraction, hide_source = plan.hide_fraction, plan.hide_source
        else:
            m = tune.measure_overlap_hide(mesh, wlike, cap_bytes=1 << 20,
                                          iters=2)
            hide_fraction, hide_source = m.hide_fraction, m.source
        sink.emit(obs.run_record(
            "train",
            arch=args.arch,
            workers=w,
            comm_mode=comp.comm_mode,
            shift_rule=comp.effective_shift_rule if comp.enabled else None,
            steps=args.steps,
            wires=acct.obs_snapshot(timed=True, quality=True),
            hide_fraction=hide_fraction,
            hide_source=hide_source,
            omega=plan.omega if plan is not None else None,
            omega_source=(plan.omega_source if plan is not None
                          else "analytic"),
            predicted_step_s=predicted_step_s,
        ))

    bridge = None
    if args.serve_fleet > 0:
        from repro.comm import SimChannel, build_transport
        from repro.serving import TrainerFleetBridge

        params_shapes = jax.eval_shape(
            lambda k: M.init_params(k, cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        downlink = build_transport(comp, cfg, SimChannel(), w=w,
                                   params_like=params_shapes)
        # the step donates the trainer's params; the fleet keeps copies
        bridge = TrainerFleetBridge(
            cfg, tmap(jnp.copy, state.params), downlink["model"],
            n_replicas=args.serve_fleet, publish_every=comp.publish_every,
            stale_k=args.stale_k, key=jax.random.PRNGKey(1),
            obs=sink,
        )

    print(f"arch={args.arch} params={M.count_params_analytic(cfg):,} "
          f"workers={w} compression={comp.enabled} "
          f"rule={comp.effective_shift_rule} comm={comp.comm_mode} "
          f"moe_wire={comp.moe_wire} act_wire={comp.act_wire} "
          f"model_wire={comp.model_wire}")

    from contextlib import nullcontext

    every = comp.drift_resync_every if comp.enabled else 0
    if recorder is not None:
        from repro.obs import recording

        loop_ctx = recording(recorder)
    else:
        loop_ctx = nullcontext()
    # host-side span around the step dispatch (+ readback when timing):
    # inert without a recorder, and obs is only imported when one exists
    step_ctx = ((lambda: obs.span("host/step"))
                if recorder is not None else nullcontext)
    t0 = time.time()
    with loop_ctx:
        for i in range(args.steps):
            ts = time.perf_counter()
            with step_ctx():
                state, metrics = step_fn(
                    state, jax.device_put(stream.batch(i), batch_sh))
                if sink is not None or recorder is not None:
                    jax.block_until_ready(state.params)
            step_s = time.perf_counter() - ts
            if bridge is not None:
                bridge.on_step(tmap(jnp.copy, state.params), i + 1)
            if sink is not None:
                sink.emit(obs.step_record(
                    i,
                    loss=float(metrics["loss"]),
                    bits=float(metrics["bits"]),
                    step_s=step_s,
                    predicted_step_s=predicted_step_s,
                    h_bar_drift=(float(metrics["h_bar_drift"])
                                 if "h_bar_drift" in metrics else None),
                    ef_err_norm=(float(metrics["ef_err_norm"])
                                 if "ef_err_norm" in metrics else None),
                    grad_sq=(float(metrics["grad_sq"])
                             if "grad_sq" in metrics else None),
                    shift_residual_sq=(
                        float(metrics["shift_residual_sq"])
                        if "shift_residual_sq" in metrics else None),
                ))
                # resync_h_bar fires inside jit at (step % N) == N-1;
                # mirror the event host-side from the same arithmetic
                if every and (i % every) == every - 1:
                    sink.emit(obs.event_record(
                        "drift_resync", i, every=every,
                    ))
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                      f"bits {float(metrics['bits']):.3e}  "
                      f"({time.time()-t0:.1f}s)")
    if bridge is not None:
        bridge.drain()
        s = bridge.stats()
        print(f"fleet[{args.serve_fleet}] wire={comp.model_wire}: "
              f"{s['publishes']} publishes, {s['resyncs']} resyncs, "
              f"{s['bytes_fraction']:.3f} of dense bytes/publish, "
              f"max staleness {s['max_staleness']} (K={args.stale_k}), "
              f"{s['tokens_served']} tokens served")
    if sink is not None:
        from repro import obs

        spans = recorder.snapshot() if recorder is not None else None
        sink.emit(obs.summary_record("train", spans=spans))
        sink.close()
        print(obs.summary_table(obs.read_jsonl(args.metrics_out),
                                name=args.arch))
        if spans:
            rows = [(n, s["count"], f"{s['mean_s']:.3e}s")
                    for n, s in sorted(spans.items())]
            print(obs.format_table("host spans", ["span", "count", "mean"],
                                   rows))
    elif recorder is not None:
        rows = [(n, s["count"], f"{s['mean_s']:.3e}s")
                for n, s in sorted(recorder.snapshot().items())]
        from repro import obs

        print(obs.format_table("host spans", ["span", "count", "mean"], rows))
    return state


if __name__ == "__main__":
    main()
