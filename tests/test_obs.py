"""The observability layer's contracts (PR 8):

  * schema: records round-trip strict JSON, the version is PINNED
    (wrong ``v`` / unknown keys / non-finite floats all fail loudly),
    and ``sanitize_tree`` is the one nan/inf -> null pass.
  * sinks: JSONL rotation keeps generations; MemorySink/TeeSink feed
    the serving bridge's event-sourced stats; the ``--check`` CLI gate
    exits non-zero on an invalid line.
  * obs OFF is bit-exact: ``build_train_step(diag=True)`` returns the
    IDENTICAL TrainState as ``diag=False`` for every shift rule x
    channel — diagnostics live in the metrics dict only.
  * obs is near-zero-cost on the jit path: ``span`` adds no ops and no
    extra compilations (trace-count pinned).
  * measured-vs-predicted: ``measure_overlap_hide`` yields a hide
    fraction in [0, 1] from the real AsyncChannel handles, and the
    fraction lands in the ``TunePlan`` (``hide_fraction``/
    ``hide_source``) and shifts ``compose_step_s``.
  * per-wire telemetry: ``Transport.obs_snapshot`` reports structural
    wire_bits AND concrete payload bytes (+ finite codec timings).
  * dedupe: ``benchmarks.common`` shares the obs strict-JSON helpers.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, tune
from repro.comm import SimChannel, build_transport
from repro.configs import get_smoke_config
from repro.configs.base import CompressionConfig, TrainConfig
from repro.data.tokens import TokenStream
from repro.launch.mesh import make_host_mesh, make_mesh, n_workers
from repro.launch.train import build_train_step, init_state
from repro.models import model as M

tmap = jax.tree_util.tree_map

RULE_CONFIGS = {
    "fixed": CompressionConfig(enabled=True, compressor="natural",
                               shift_rule="fixed"),
    "diana": CompressionConfig(enabled=True, compressor="natural",
                               shift_rule="diana", shift_alpha=0.25),
    "rand_diana": CompressionConfig(enabled=True, compressor="natural",
                                    shift_rule="rand_diana", shift_p=0.5),
    "ef21": CompressionConfig(enabled=True, compressor="topk",
                              compressor_kwargs=(("q", 0.25),),
                              shift_rule="ef21"),
    "efbv": CompressionConfig(enabled=True, compressor="natural",
                              shift_rule="efbv", efbv_eta=0.5, efbv_nu=0.9),
}


def _wtree(key, w=4):
    return {
        "a": jax.random.normal(key, (w, 40)),
        "b": {
            "c": jax.random.normal(jax.random.fold_in(key, 1), (w, 3, 5)),
            "d": jax.random.normal(jax.random.fold_in(key, 2), (w,)),
        },
    }


# ---------------------------------------------------------------------------
# Schema: round-trip, version pinning, strictness
# ---------------------------------------------------------------------------


def test_record_constructors_round_trip_strict_json():
    recs = [
        obs.run_record("train", arch="qwen3", workers=4),
        obs.step_record(3, run="train", loss=1.5, step_s=0.01),
        obs.event_record("resync_requested", 7, replica=0, reason="staleness"),
        obs.summary_record("train", n_steps=8),
    ]
    for rec in recs:
        line = json.dumps(rec, allow_nan=False)      # strict-serializable
        assert obs.validate_record(json.loads(line)) == rec
        assert rec["v"] == obs.SCHEMA_VERSION
        assert rec["kind"] in obs.RECORD_KINDS


def test_schema_version_is_pinned():
    rec = obs.step_record(0, loss=1.0)
    stale = {**rec, "v": obs.SCHEMA_VERSION + 1}
    with pytest.raises(ValueError, match="version"):
        obs.validate_record(stale)
    with pytest.raises(ValueError, match="version"):
        obs.validate_record({**rec, "v": None})


def test_schema_rejects_malformed_records():
    with pytest.raises(ValueError, match="kind"):
        obs.validate_record({"v": obs.SCHEMA_VERSION, "kind": "bogus",
                             "data": {}})
    with pytest.raises(ValueError, match="unknown record keys"):
        obs.validate_record({**obs.step_record(0), "loss": 1.0})
    with pytest.raises(ValueError, match="missing required"):
        obs.validate_record({"v": obs.SCHEMA_VERSION, "kind": "event",
                             "step": 0, "data": {}})
    with pytest.raises(ValueError, match="step"):
        obs.validate_record({"v": obs.SCHEMA_VERSION, "kind": "step",
                             "step": -1, "data": {}})
    with pytest.raises(ValueError, match="non-finite"):
        obs.validate_record({"v": obs.SCHEMA_VERSION, "kind": "step",
                             "step": 0, "data": {"loss": float("nan")}})


def test_sanitize_tree_and_finite_or_none():
    assert obs.finite_or_none(float("inf")) is None
    assert obs.finite_or_none(float("nan")) is None
    assert obs.finite_or_none(2) == 2.0
    out = obs.sanitize_tree({
        "nan": float("nan"),
        "jax": jnp.float32(1.5),
        "np": np.float64(2.5),
        "tup": (1, float("inf")),
        "keep": {"s": "x", "b": True, "n": None, "i": 7},
    })
    assert out["nan"] is None
    assert out["jax"] == 1.5 and isinstance(out["jax"], float)
    assert out["np"] == 2.5
    assert out["tup"] == [1, None]
    assert out["keep"] == {"s": "x", "b": True, "n": None, "i": 7}
    # the record constructors sanitize: device scalars are writable
    rec = obs.step_record(0, loss=jnp.float32(3.0), bad=float("inf"))
    assert rec["data"] == {"loss": 3.0, "bad": None}


# ---------------------------------------------------------------------------
# Sinks
# ---------------------------------------------------------------------------


def test_jsonl_sink_rotation_and_read_back(tmp_path):
    path = str(tmp_path / "run.jsonl")
    sink = obs.JsonlSink(path, rotate_bytes=512, keep=2)
    for i in range(64):
        sink.emit(obs.step_record(i, loss=float(i)))
    sink.close()
    assert os.path.exists(path)
    assert os.path.exists(path + ".1")          # rotated generation
    assert not os.path.exists(path + ".3")      # keep=2 bounds the set
    live = obs.read_jsonl(path)                 # every line schema-valid
    assert all(r["kind"] == "step" for r in live)
    n, errors = obs.check_jsonl(path + ".1")
    assert n > 0 and errors == []


def test_check_jsonl_collects_all_failures(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    good = json.dumps(obs.step_record(0, loss=1.0))
    with open(path, "w") as f:
        f.write(good + "\n")
        f.write("not json\n")
        f.write(json.dumps({"v": 999, "kind": "step", "step": 1,
                            "data": {}}) + "\n")
    n, errors = obs.check_jsonl(path)
    assert n == 1 and len(errors) == 2
    with pytest.raises(ValueError):
        obs.read_jsonl(path)


def test_export_cli_check_gate(tmp_path):
    from repro.obs import export

    good = str(tmp_path / "good.jsonl")
    sink = obs.JsonlSink(good)
    sink.emit(obs.run_record("r", workers=1))
    sink.emit(obs.step_record(0, run="r", loss=0.5, step_s=0.01,
                              predicted_step_s=0.02))
    sink.close()
    assert export.main(["--check", good]) == 0

    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"v": 0, "kind": "step", "step": 0, "data": {}}\n')
    assert export.main(["--check", bad]) == 1


def test_memory_and_tee_sinks():
    mem, mirror = obs.MemorySink(), obs.MemorySink()
    tee = obs.TeeSink(mem, None, mirror)        # None sinks are dropped
    tee.emit(obs.event_record("publish", 1, bytes=10.0))
    tee.emit(obs.event_record("fleet_resync", 2, replica=0))
    tee.emit(obs.step_record(3, loss=1.0))
    assert [r["name"] for r in mem.events()] == ["publish", "fleet_resync"]
    assert len(mem.events("publish")) == 1
    assert len(mem.by_kind("step")) == 1
    assert mirror.records == mem.records


def test_typed_metrics():
    m = obs.Metrics()
    m.counter("resyncs").inc()
    m.counter("resyncs").inc(2)
    m.gauge("staleness").set(3.0)
    for x in (0.1, 0.2, 0.3):
        m.histogram("step_s").observe(x)
    m.histogram("step_s").observe(float("nan"))  # ignored, not poisoned
    snap = m.snapshot()
    assert snap["resyncs"] == 3.0
    assert snap["staleness"] == 3.0
    assert snap["step_s"]["count"] == 3
    assert snap["step_s"]["mean"] == pytest.approx(0.2)
    assert snap["step_s"]["min"] == 0.1 and snap["step_s"]["max"] == 0.3
    with pytest.raises(ValueError, match="negative"):
        m.counter("resyncs").inc(-1)
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("resyncs")
    # the snapshot is record-ready
    obs.validate_record(obs.summary_record("metrics", **snap))


# ---------------------------------------------------------------------------
# Obs OFF is bit-exact; spans are free on the jit path
# ---------------------------------------------------------------------------


def _train_setup(comp):
    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    tcfg = TrainConfig(learning_rate=1e-2, total_steps=10, warmup_steps=2,
                       compression=comp)
    mesh = make_host_mesh()
    return cfg, tcfg, mesh, n_workers(mesh)


@pytest.mark.parametrize("comm_mode", ["sim", "dense"])
@pytest.mark.parametrize("name", sorted(RULE_CONFIGS))
def test_diag_metrics_leave_state_bit_exact(name, comm_mode):
    """THE obs-off contract: ``diag=True`` (what ``--metrics_out`` jits)
    returns a TrainState IDENTICAL to ``diag=False`` for every rule x
    channel — h_bar drift / EF error norms are read-only taps."""
    comp = dataclasses.replace(RULE_CONFIGS[name], comm_mode=comm_mode)
    cfg, tcfg, mesh, w = _train_setup(comp)
    state = init_state(jax.random.PRNGKey(0), cfg, tcfg, w)
    stream = TokenStream(cfg, 16, 4)

    step_off = jax.jit(build_train_step(cfg, tcfg, mesh, w, diag=False))
    step_on = jax.jit(build_train_step(cfg, tcfg, mesh, w, diag=True))
    s_off, m_off = step_off(state, stream.batch(0))
    s_on, m_on = step_on(state, stream.batch(0))

    for a, b in zip(jax.tree_util.tree_leaves(s_off),
                    jax.tree_util.tree_leaves(s_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # diagnostics ride the METRICS dict only, as a superset
    assert set(m_off) <= set(m_on)
    assert np.isfinite(float(m_on["ef_err_norm"]))
    if s_on.h_bar is not None:
        assert np.isfinite(float(m_on["h_bar_drift"]))


def test_span_adds_no_ops_and_no_recompilation():
    """``span`` inside jit is pure trace metadata: same lowering as the
    bare function, ONE trace across repeated calls, recording on/off."""
    traces = []

    def g(x):
        traces.append(1)
        with obs.span("test/phase"):
            return x * 2.0 + 1.0

    f = jax.jit(g)
    x = jnp.arange(4, dtype=jnp.float32)
    y0 = f(x)
    y1 = f(x + 1)
    with obs.recording(obs.SpanRecorder()):
        y2 = f(x + 2)
    assert sum(traces) == 1                     # no extra compilations
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(x) * 2 + 1)
    np.testing.assert_array_equal(np.asarray(y2),
                                  (np.asarray(x) + 2) * 2 + 1)
    # and the math is the bare function's math
    bare = jax.jit(lambda x: x * 2.0 + 1.0)(x + 1)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(bare))


def test_span_times_host_work_only_when_recording():
    rec = obs.SpanRecorder()
    with obs.span("host/untimed"):              # no recorder active
        pass
    assert rec.spans == {}
    with obs.recording(rec):
        for _ in range(3):
            with obs.span("host/timed"):
                pass
    assert obs.active_recorder() is None        # restored on exit
    snap = rec.snapshot()
    assert snap["host/timed"]["count"] == 3
    assert snap["host/timed"]["total_s"] >= 0.0

    def traced(x):
        with obs.span("host/traced"):           # tracing time, not compute
            return x + 1.0

    with obs.recording(rec):
        jax.jit(traced)(jnp.float32(0.0))
    assert "host/traced" not in rec.snapshot()


def test_stamp_recorder_windows():
    rec = obs.StampRecorder()
    with rec.stamp("reduce_start"):
        pass
    with rec.stamp("finish"):
        pass
    assert len(rec.windows("reduce_start")) == 1
    assert len(rec.windows("finish")) == 1
    assert rec.total("finish") >= 0.0
    rec.clear()
    assert rec.events == []


# ---------------------------------------------------------------------------
# Measured hide fraction -> cost model -> TunePlan
# ---------------------------------------------------------------------------


def test_measure_overlap_hide_in_unit_interval():
    mesh = make_host_mesh()
    wtree = _wtree(jax.random.PRNGKey(0), w=2)
    m = tune.measure_overlap_hide(mesh, wtree, cap_bytes=1 << 14, iters=1,
                                  n_compute=64)
    assert 0.0 <= m.hide_fraction <= 1.0
    assert m.source == "measured"
    assert m.compute_s > 0.0 and m.comm_s > 0.0 and m.overlapped_s > 0.0


def test_compose_step_s_uses_measured_hide():
    full = tune.compose_step_s(1.0, 1.0, True, hide=1.0)
    none = tune.compose_step_s(1.0, 1.0, True, hide=0.0)
    nominal = tune.compose_step_s(1.0, 1.0, True)
    assert full < nominal < none
    assert nominal == tune.compose_step_s(1.0, 1.0, True,
                                          hide=tune.OVERLAP_HIDE)
    # without overlap the hide fraction must not matter
    assert tune.compose_step_s(1.0, 1.0, False, hide=1.0) == \
        tune.compose_step_s(1.0, 1.0, False, hide=0.0)


def test_measured_hide_lands_in_tune_plan(tmp_path):
    """Satellite: a measured hide fraction is plumbed through
    ``search_plan`` into the produced ``TunePlan`` and survives the
    strict-JSON round trip (what ``repro.tune`` consumes in place of
    the nominal constant)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    wtree = _wtree(jax.random.PRNGKey(0), w=4)
    kw = dict(modes=("dense", "q8_ring_overlap"), bucket_grid=(1 << 20,),
              link=tune.LinkModel.nominal(), verify_top=0,
              # a nonzero compute half so the hide fraction has comm to
              # tuck under it (None analysis contributes zero compute);
              # small enough that no hide value clamps the comm to zero
              analysis={"flops": 2e8, "bytes": 0.0})
    plan = tune.search_plan(CompressionConfig(), wtree, mesh, 4,
                            hide=0.42, hide_source="measured", **kw)
    assert plan.hide_fraction == pytest.approx(0.42)
    assert plan.hide_source == "measured"

    nominal = tune.search_plan(CompressionConfig(), wtree, mesh, 4, **kw)
    assert nominal.hide_fraction is None
    assert nominal.hide_source == "nominal"
    # the fraction changes the overlap candidates' predictions
    t = {r["comm_mode"]: r["predicted_step_s"] for r in plan.candidates}
    t0 = {r["comm_mode"]: r["predicted_step_s"] for r in nominal.candidates}
    assert t["q8_ring_overlap"] != t0["q8_ring_overlap"]
    assert t["dense"] == t0["dense"]            # no overlap -> no effect

    rt = tune.load_plan(tune.save_plan(plan, str(tmp_path / "p.json")))
    assert rt.hide_fraction == pytest.approx(0.42)
    assert rt.hide_source == "measured"


# ---------------------------------------------------------------------------
# Per-wire telemetry
# ---------------------------------------------------------------------------


def test_transport_obs_snapshot_bits_payload_timings():
    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    shapes = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    comp = CompressionConfig(enabled=False, model_wire="q8", publish_every=2)
    transport = build_transport(comp, cfg, SimChannel(), params_like=shapes)
    snap = transport.obs_snapshot()
    rec = snap["model"]
    assert rec["topology"] == "broadcast"
    assert rec["wire_bits"] > 0.0
    assert rec["payload_bytes"] > 0.0
    # the container is at least as wide as the protocol bits it carries
    assert rec["payload_bytes"] >= rec["wire_bits"] / 8.0
    assert rec["encode_s"] is None              # untimed snapshot is AOT

    timed = transport.obs_snapshot(timed=True)["model"]
    assert timed["encode_s"] > 0.0 and np.isfinite(timed["encode_s"])
    assert timed["decode_s"] >= 0.0 and np.isfinite(timed["decode_s"])
    # the snapshot is record-ready for the run header
    obs.validate_record(obs.run_record("t", wires=snap))


def test_grad_wire_payload_and_codec_timings():
    comp = RULE_CONFIGS["diana"]
    q, rule = comp.make()
    params_like = {"a": jax.ShapeDtypeStruct((40,), jnp.float32),
                   "b": jax.ShapeDtypeStruct((3, 5), jnp.float32)}
    transport = build_transport(comp, None, SimChannel(), rule=rule,
                                msg_codec=q, w=4, params_like=params_like)
    wire = transport["grad"]
    assert wire.payload_nbytes() > 0.0
    t = wire.codec_timings(jax.random.PRNGKey(0))
    assert t["encode_s"] > 0.0 and t["decode_s"] >= 0.0
    # a traffic-free wire reports Nones, not zeros
    bare = build_transport(comp, None, SimChannel(), rule=rule,
                           msg_codec=q, w=4)["grad"]
    assert bare.codec_timings() == {"encode_s": None, "decode_s": None}


def test_fused_grad_wire_snapshot_encode_stage_gone():
    """Schema pin for the fused-backward mode: the grad wire reports
    ``fused: True`` and EXACT ZERO standalone encode/decode seconds —
    the deleted stage — while payload accounting is unchanged vs the
    post-hoc overlap mode; non-fused wires report ``fused: False``."""
    import dataclasses

    from repro.comm import make_channel

    params_like = {"a": jax.ShapeDtypeStruct((40,), jnp.float32),
                   "b": jax.ShapeDtypeStruct((3, 5), jnp.float32)}
    snaps = {}
    for mode in ("q8_ring_overlap", "q8_ring_fused_vjp"):
        comp = dataclasses.replace(RULE_CONFIGS["diana"], comm_mode=mode)
        q, rule = comp.make()
        transport = build_transport(comp, None, make_channel(comp),
                                    rule=rule, msg_codec=q, w=4,
                                    params_like=params_like)
        snaps[mode] = transport.obs_snapshot(timed=True)["grad"]

    fused = snaps["q8_ring_fused_vjp"]
    posthoc = snaps["q8_ring_overlap"]
    assert fused["fused"] is True
    assert posthoc["fused"] is False
    assert fused["encode_s"] == 0.0 and fused["decode_s"] == 0.0
    assert posthoc["encode_s"] > 0.0
    # the wire payload itself is unchanged — only the launch is deleted
    assert fused["wire_bits"] == posthoc["wire_bits"] > 0.0
    assert fused["payload_bytes"] == posthoc["payload_bytes"] > 0.0
    assert fused["codec"] == posthoc["codec"]
    # record-ready for the run header, strict schema
    obs.validate_record(obs.run_record("t", wires=snaps))


# ---------------------------------------------------------------------------
# Serving fleet: event-sourced accounting
# ---------------------------------------------------------------------------


def test_fleet_bridge_event_sourced_stats():
    from repro.serving import TrainerFleetBridge
    from repro.comm import Wire, wire_flag_codec

    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    wire = Wire(name="model", topology="broadcast",
                codec=wire_flag_codec("q8"), channel=SimChannel())
    mirror = obs.MemorySink()
    bridge = TrainerFleetBridge(cfg, params, wire, n_replicas=2,
                                publish_every=2, stale_k=4, obs=mirror)

    leaves, treedef = jax.tree_util.tree_flatten(params)
    for i in range(1, 7):
        leaves = [x + 1e-3 for x in leaves]
        bridge.on_step(jax.tree_util.tree_unflatten(treedef, leaves), i)
    stats = bridge.stats()

    # stats IS the event stream: counts match the records verbatim
    assert stats["publishes"] == len(bridge.events.events("publish")) == 3
    assert stats["resyncs"] == len(bridge.events.events("fleet_resync"))
    assert len(bridge.events.events("fleet_bootstrap")) == 1
    assert stats["obs_events"]["publish"] == 3
    assert len(stats["err_rel"]) == 3
    assert stats["delta_bytes_per_publish"] > 0.0
    # the caller's sink saw the SAME stream (tee) and it is schema-valid
    assert mirror.records == bridge.events.records
    for rec in mirror.records:
        obs.validate_record(rec)


# ---------------------------------------------------------------------------
# Exports
# ---------------------------------------------------------------------------


def _fake_run_records():
    recs = [obs.run_record(
        "train", workers=4,
        wires={"grad": {"topology": "allreduce", "codec": "Natural",
                        "wire_bits": 1000.0, "payload_bytes": 500.0,
                        "encode_s": 1e-4, "decode_s": 2e-4,
                        "omega_hat": 0.11, "nmse": 0.09}},
        hide_fraction=0.8, hide_source="measured",
        omega=0.13, omega_source="measured",
    )]
    for i in range(4):
        recs.append(obs.step_record(i, run="train", loss=2.0 - 0.1 * i,
                                    bits=100.0 * (i + 1), step_s=0.01,
                                    predicted_step_s=0.012,
                                    grad_sq=4.0,
                                    shift_residual_sq=1.0 / (i + 1)))
    recs.append(obs.event_record("drift_resync", 3, every=4))
    recs.append(obs.event_record("publish", 2, bytes=10.0, err_rel=0.01))
    return recs


def test_summarize_measured_vs_predicted():
    s = obs.summarize(_fake_run_records(), name="train")["data"]
    assert s["n_steps"] == 4
    assert s["step_s"]["mean"] == pytest.approx(0.01)
    assert s["predicted_step_s"]["mean"] == pytest.approx(0.012)
    assert s["predicted_over_actual"]["mean"] == pytest.approx(1.2)
    assert s["final_loss"] == pytest.approx(1.7)
    assert s["final_bits"] == pytest.approx(400.0)
    assert s["hide_fraction"] == pytest.approx(0.8)
    assert s["hide_source"] == "measured"
    assert s["wires"]["grad"]["payload_bytes"] == 500.0
    assert s["wires"]["grad"]["omega_hat"] == pytest.approx(0.11)
    assert s["events"] == {"drift_resync": 1, "publish": 1}
    # the quality aggregate: measured omega from the run header, the
    # shift-residual trajectory from the step stream
    assert s["omega"] == pytest.approx(0.13)
    assert s["omega_source"] == "measured"
    assert s["shift_residual_first"] == pytest.approx(1.0)
    assert s["shift_residual_last"] == pytest.approx(0.25)
    assert s["shift_residual_sq"]["count"] == 4
    assert s["shift_residual_over_grad"]["mean"] == pytest.approx(
        (1.0 + 0.5 + 1.0 / 3.0 + 0.25) / 4.0 / 4.0)


def test_summary_table_and_prometheus_text():
    recs = _fake_run_records()
    table = obs.summary_table(recs, name="train")
    for needle in ("wire grad", "predicted/actual", "event drift_resync",
                   "overlap hide fraction", "omega", "shift resid/grad",
                   "omega_hat 0.11"):
        assert needle in table
    prom = obs.prometheus_text(recs, name="train")
    assert '# TYPE repro_overlap_hide_fraction gauge' in prom
    assert 'repro_overlap_hide_fraction{run="train"} 0.8' in prom
    assert 'repro_wire_payload_bytes_per_step{run="train",wire="grad"}' in prom
    assert 'repro_events_total{run="train",event="publish"} 1' in prom
    # schema pins for the quality gauges (dashboards key on these names)
    assert 'repro_omega{run="train"} 0.13' in prom
    assert 'repro_wire_omega_hat{run="train",wire="grad"} 0.11' in prom
    assert 'repro_wire_nmse{run="train",wire="grad"} 0.09' in prom
    assert '# TYPE repro_shift_residual_sq gauge' in prom
    assert '# TYPE repro_shift_residual_over_grad gauge' in prom
    # exposition format: every non-comment line is `name{labels} value`
    for line in prom.strip().splitlines():
        if not line.startswith("#"):
            assert "{" in line and line.rsplit(" ", 1)[1]


# ---------------------------------------------------------------------------
# Dedupe: benchmarks share the obs strict-JSON helpers
# ---------------------------------------------------------------------------


def test_bench_common_shares_obs_helpers(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import common

    assert common.finite_or_none is obs.finite_or_none
    # print_table renders through the same formatter as the obs summary
    assert common.format_table is obs.format_table
    assert common.write_strict_json is obs.write_strict_json
    # tune plans sanitize through the same pass
    from repro.tune import plan as tune_plan
    assert tune_plan._finite_tree({"x": float("inf")}) == {"x": None}
