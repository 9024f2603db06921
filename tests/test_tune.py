"""repro.tune tests: the predictor's wire accounting against the live
codec payloads (every registered comm mode), TunePlan persistence +
fingerprint cache semantics, the plan search with injected
measurements, the auto comm-mode plumbing, and the drift-resync
satellite (bounded h_bar drift over lossy aggregation)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.comm import MeshChannel, make_channel, resync_h_bar
from repro.comm.wire import encode_workers, leaf_key
from repro.configs.base import CompressionConfig
from repro.core.shift_rules import DianaShift
from repro.core.compressors import NaturalCompression
from repro.launch.mesh import make_mesh
from repro.tune.model import Candidate, TUNABLE_MODES, predicted_wire_bits, wire_codec

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wtree(key, w=3):
    """Tiny worker-stacked tree (small grids: the fused modes run
    interpret-mode Pallas per leaf on CPU)."""
    return {
        "a": jax.random.normal(key, (w, 40)),
        "b": {
            "c": jax.random.normal(jax.random.fold_in(key, 1), (w, 3, 5)),
            "d": jax.random.normal(jax.random.fold_in(key, 2), (w,)),
        },
    }


def _candidate(mode: str) -> Candidate:
    if mode == "ef21":  # ef21's wire is the configured CONTRACTIVE codec
        return Candidate(mode, compressor="topk",
                         compressor_kwargs=(("q", 0.25),))
    return Candidate(mode, bucket_bytes=64)


# ---------------------------------------------------------------------------
# The wire-accounting contract (satellite): predicted == live, per mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", TUNABLE_MODES)
def test_predicted_wire_bits_match_live_payloads(mode):
    """For EVERY registered comm mode, the tuner's AOT wire accounting
    must equal the structural wire_bits of the CONCRETE payloads the
    mode's codec emits on the same tree — the test that catches drift
    between the cost model and the wire protocol."""
    key = jax.random.PRNGKey(5)
    wtree = _wtree(key)
    cand = _candidate(mode)
    codec = wire_codec(cand)
    live = 0.0
    for i, leaf in enumerate(jax.tree_util.tree_leaves(wtree)):
        payload, _ = encode_workers(codec, leaf_key(key, i), leaf)
        live += float(codec.wire_bits(payload))
    assert live == predicted_wire_bits(cand, wtree), mode


def test_fused_mode_charges_zero_standalone_encode():
    """The fused-VJP mode's encode runs inside the backward pass —
    the predictor must charge it ZERO standalone-encode time while
    still charging the post-hoc compressed modes, and must never
    perturb analysis-free (pure wire) rankings with the new term."""
    from repro.tune.measure import DeviceRates, LinkModel
    from repro.tune.model import encode_time_s, predict_step

    wtree = _wtree(jax.random.PRNGKey(2))
    rates = DeviceRates.nominal()
    fused = _candidate("q8_ring_fused_vjp")
    posthoc = _candidate("q8_ring_overlap")
    assert fused.fused and fused.overlap and not posthoc.fused

    assert encode_time_s(fused, wtree, rates) == 0.0
    assert encode_time_s(_candidate("dense"), wtree, rates) == 0.0
    assert encode_time_s(posthoc, wtree, rates) > 0.0
    assert encode_time_s(_candidate("q8_ring"), wtree, rates) > 0.0

    link = LinkModel.nominal()
    analysis = {"flops": 1e9, "bytes": 1e8}
    p_fused = predict_step(fused, wtree, link, 4, analysis=analysis,
                           rates=rates)
    p_post = predict_step(posthoc, wtree, link, 4, analysis=analysis,
                          rates=rates)
    assert p_fused.encode_s == 0.0
    assert p_post.encode_s > 0.0
    # same codec, same payload — the predictions differ ONLY by the
    # deleted encode stage and the bucket granularity
    assert p_fused.wire_bytes == p_post.wire_bytes
    # analysis-free predictions stay pure wire orderings (no encode)
    assert predict_step(posthoc, wtree, link, 4).encode_s == 0.0
    # per-leaf buckets: one launch per leaf, regardless of bucket_bytes
    n_leaves = len(jax.tree_util.tree_leaves(wtree))
    assert p_fused.n_buckets == n_leaves


def test_default_candidates_include_fused_mode():
    comp = CompressionConfig(enabled=True, compressor="natural",
                             shift_rule="diana")
    wtree = _wtree(jax.random.PRNGKey(0))
    cands = tune.default_candidates(comp, wtree)
    fused = [c for c in cands if c.comm_mode == "q8_ring_fused_vjp"]
    assert fused, [c.comm_mode for c in cands]
    assert all("per-leaf" in c.label for c in fused)


def test_candidate_rejects_unknown_mode_naming_modes():
    with pytest.raises(ValueError) as ei:
        Candidate("carrier_pigeon")
    for m in TUNABLE_MODES:
        assert m in str(ei.value)


def test_extra_wire_bits_match_live_payloads():
    """The grad-wire invariant above, extended to EVERY registered wire:
    the tuner's per-wire AOT charge (``extra_wire_bits``) must equal the
    structural wire_bits of the CONCRETE payloads each wire's codec
    emits on its declared traffic — and both must equal the Transport's
    own ``per_wire_bits`` accounting table."""
    from repro.comm import build_transport, wire_flag_codec
    from repro.comm.wire import encode_meta_free
    from repro.configs import get_smoke_config
    from repro.tune.model import extra_wire_bits

    cfg = get_smoke_config("qwen2-moe-a2.7b").with_(dtype="float32")
    comp = CompressionConfig(comm_mode="dense", shift_rule="diana",
                             moe_wire="q8", act_wire="q8")
    transport = build_transport(comp, cfg, None, w=2, tokens_per_worker=64)
    traffic = transport.extra_traffic()
    assert set(traffic) == {"moe", "act"}

    key = jax.random.PRNGKey(9)
    live = {}
    for name, decl in traffic.items():
        codec = wire_flag_codec("q8")
        bits = 0.0
        for sds, count in decl:
            x = jax.random.normal(key, sds.shape, dtype=sds.dtype)
            payload = encode_meta_free(codec, key, x)
            bits += count * float(codec.wire_bits(payload))
        live[name] = bits
        # structural accounting on the Transport agrees per wire
        assert transport.per_wire_bits()[name] == bits, name

    cand = Candidate("dense", moe_wire="q8", act_wire="q8")
    assert extra_wire_bits(cand, traffic) == sum(live.values())
    # a "none" flag still moves the payload — at identity width
    cand_none = Candidate("dense")
    dense_transport = build_transport(
        CompressionConfig(comm_mode="dense", shift_rule="diana",
                          moe_wire="dense", act_wire="dense"),
        cfg, None, w=2, tokens_per_worker=64)
    assert extra_wire_bits(cand_none, traffic) == pytest.approx(
        sum(dense_transport.per_wire_bits()[n] for n in ("moe", "act")))


def test_candidate_rejects_unknown_wire_flag_verbatim():
    from repro.comm import WIRE_CODEC_FLAGS

    for field in ("moe_wire", "act_wire"):
        with pytest.raises(ValueError) as ei:
            Candidate("dense", **{field: "carrier_pigeon"})
        assert "carrier_pigeon" in str(ei.value)
        for f in WIRE_CODEC_FLAGS:
            assert f in str(ei.value)


# ---------------------------------------------------------------------------
# TunePlan persistence + fingerprint cache
# ---------------------------------------------------------------------------


def _plan(fp="f" * 64, mode="dense", **kw):
    defaults = dict(
        fingerprint=fp, comm_mode=mode, overlap_bucket_bytes=4 << 20,
        randk_q=0.05, q8_block_rows=64, efbv_eta=1.0, efbv_nu=1.0,
        predicted_step_s=1e-3,
    )
    defaults.update(kw)
    return tune.TunePlan(**defaults)


def test_plan_json_round_trip_strict(tmp_path):
    plan = _plan(measured_step_s=2e-3,
                 candidates=({"label": "dense", "chosen": True,
                              "measured_step_s": float("inf")},))
    path = tune.save_plan(plan, str(tmp_path / "p.json"))
    # the artifact is STRICT JSON: non-finite floats become null
    raw = open(path).read()
    assert "Infinity" not in raw and "NaN" not in raw
    loaded = tune.load_plan(path)
    assert loaded.comm_mode == plan.comm_mode
    assert loaded.fingerprint == plan.fingerprint
    assert loaded.candidates[0]["measured_step_s"] is None


def test_plan_version_and_unknown_fields_rejected():
    d = _plan().to_dict()
    d["version"] = 0
    with pytest.raises(ValueError, match="version"):
        tune.TunePlan.from_dict(d)
    d = _plan().to_dict()
    d["surprise"] = 1
    with pytest.raises(ValueError, match="surprise"):
        tune.TunePlan.from_dict(d)


def test_fingerprint_sensitivity():
    mesh = make_mesh((1, 1), ("data", "model"))
    params = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
    fp = tune.plan_fingerprint(params, mesh, 4, "natural")
    assert fp == tune.plan_fingerprint(params, mesh, 4, "natural")
    # every keyed ingredient must change the fingerprint
    assert fp != tune.plan_fingerprint(params, mesh, 8, "natural")
    assert fp != tune.plan_fingerprint(params, mesh, 4, "topk")
    other = {"w": jax.ShapeDtypeStruct((8, 5), jnp.float32)}
    assert fp != tune.plan_fingerprint(other, mesh, 4, "natural")
    # the SEARCH SPACE is keyed too: a narrowed --tune_modes run must
    # not satisfy a later full-grid lookup on the same workload
    assert fp != tune.plan_fingerprint(
        params, mesh, 4, "natural", search={"modes": ("dense",)}
    )


def test_autotune_restricted_modes_do_not_poison_full_cache(tmp_path):
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = CompressionConfig(comm_mode="auto")
    params = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
    kw = dict(cache_dir=str(tmp_path), link=tune.LinkModel.nominal(),
              verify_top=0)
    _, hit = tune.autotune(comp, params, mesh, 2,
                           modes=("dense", "randk_shared"), **kw)
    assert not hit
    # same workload, FULL grid: the narrowed plan must miss
    _, hit_full = tune.autotune(comp, params, mesh, 2, **kw)
    assert not hit_full
    # and each keeps its own cache entry
    assert len(list(tmp_path.glob("tuneplan_*.json"))) == 2


def test_autotune_lazy_analysis_only_on_miss(tmp_path):
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = CompressionConfig(comm_mode="auto")
    params = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
    calls = []

    def analysis_fn():
        calls.append(1)
        return {"flops": 1e9, "bytes": 1e8}

    kw = dict(cache_dir=str(tmp_path), modes=("dense", "q8_ring"),
              link=tune.LinkModel.nominal(), verify_top=0,
              analysis_fn=analysis_fn, rates_fn=tune.DeviceRates.nominal)
    plan, hit = tune.autotune(comp, params, mesh, 2, **kw)
    assert not hit and len(calls) == 1
    assert plan.predicted_step_s > 0.0  # the compute term is really in
    _, hit2 = tune.autotune(comp, params, mesh, 2, **kw)
    assert hit2 and len(calls) == 1  # a hit stays free of analysis work


def test_cached_plan_miss_on_corrupt_or_mismatched_file(tmp_path):
    fp = "a" * 64
    path = tune.cache_path(str(tmp_path), fp)
    assert tune.load_cached_plan(str(tmp_path), fp) is None
    tune.save_plan(_plan(fp="b" * 64), path)  # wrong fingerprint inside
    assert tune.load_cached_plan(str(tmp_path), fp) is None
    with open(path, "w") as f:
        f.write("{not json")
    assert tune.load_cached_plan(str(tmp_path), fp) is None
    tune.save_plan(_plan(fp=fp), path)
    assert tune.load_cached_plan(str(tmp_path), fp).fingerprint == fp


# ---------------------------------------------------------------------------
# The search + autotune cache
# ---------------------------------------------------------------------------


def test_search_plan_measured_winner_and_evidence():
    """Injected measurements decide among the verified candidates; the
    plan records predicted AND measured times with the winner marked."""
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = CompressionConfig()
    wtree = _wtree(jax.random.PRNGKey(0), w=4)
    fake = {"dense": 5e-3, "randk_shared": 2e-3, "q8_ring": 1e-3}
    plan = tune.search_plan(
        comp, wtree, mesh, 4,
        modes=("dense", "randk_shared", "q8_ring"), randk_grid=(0.05,),
        link=tune.LinkModel.nominal(), verify_top=3,
        measure_fn=lambda c, t, k: fake[c.comm_mode],
    )
    assert plan.comm_mode == "q8_ring"
    assert plan.measured_step_s == pytest.approx(1e-3)
    chosen = [r for r in plan.candidates if r["chosen"]]
    assert len(chosen) == 1 and chosen[0]["comm_mode"] == "q8_ring"
    for row in plan.candidates:
        assert row["predicted_step_s"] >= 0.0
        assert row["measured_step_s"] is not None  # verify_top covered all


def test_search_plan_prediction_only_when_verify_zero():
    mesh = make_mesh((1, 1), ("data", "model"))
    wtree = _wtree(jax.random.PRNGKey(0), w=4)
    boom = lambda c, t, k: (_ for _ in ()).throw(AssertionError)  # noqa
    plan = tune.search_plan(
        CompressionConfig(), wtree, mesh, 4,
        modes=("dense", "randk_shared"), link=tune.LinkModel.nominal(),
        verify_top=0, measure_fn=boom,
    )
    assert plan.measured_step_s is None
    # per-worker compressed payloads are smaller than dense: with a
    # nominal bandwidth-dominated link the sparser mode must rank first
    assert plan.comm_mode == "randk_shared"


def test_measured_omega_lands_in_tune_plan(tmp_path):
    """Satellite: a measured ``omega_hat`` replaces the analytic
    certificate in the EF-BV eta/nu derivation, the plan records the
    value AND its provenance (v6 fields), and both survive the
    strict-JSON round trip."""
    mesh = make_mesh((1, 1), ("data", "model"))
    wtree = _wtree(jax.random.PRNGKey(0), w=4)
    comp = CompressionConfig(compressor="natural")
    kw = dict(modes=("efbv",), link=tune.LinkModel.nominal(),
              verify_top=0)

    analytic = tune.search_plan(comp, wtree, mesh, 4, **kw)
    assert analytic.omega == pytest.approx(0.125)   # natural certificate
    assert analytic.omega_source == "analytic"
    assert analytic.efbv_eta == pytest.approx(1.0 / 1.125)

    measured = tune.search_plan(comp, wtree, mesh, 4, omega=0.5, **kw)
    assert measured.omega == pytest.approx(0.5)
    assert measured.omega_source == "measured"
    # the damping really runs on the observed variance, not the bound
    assert measured.efbv_eta == pytest.approx(1.0 / 1.5)
    assert measured.efbv_eta != analytic.efbv_eta

    rt = tune.load_plan(tune.save_plan(measured, str(tmp_path / "p.json")))
    assert rt.omega == pytest.approx(0.5)
    assert rt.omega_source == "measured"


def test_no_certificate_codec_warns_with_structured_event():
    """Satellite: a codec with NO unbiased certificate (TopK has only
    ``delta``) yields ``omega_source="none"`` and a structured
    ``omega_unavailable`` obs event naming the codec — a warning a
    dashboard can alert on, not a lost stdout line."""
    from repro import obs

    mesh = make_mesh((1, 1), ("data", "model"))
    wtree = _wtree(jax.random.PRNGKey(0), w=4)
    comp = CompressionConfig(compressor="topk",
                             compressor_kwargs=(("q", 0.25),))
    sink = obs.MemorySink()
    plan = tune.search_plan(comp, wtree, mesh, 4, modes=("dense", "ef21"),
                            link=tune.LinkModel.nominal(), verify_top=0,
                            obs_sink=sink)
    assert plan.omega is None
    assert plan.omega_source == "none"
    events = sink.events("omega_unavailable")
    assert len(events) == 1
    assert events[0]["data"]["codec"] == "TopK"
    assert events[0]["data"]["compressor"] == "topk"
    obs.validate_record(events[0])


def test_autotune_measured_omega_lazy_only_on_miss(tmp_path):
    """``omega_fn`` mirrors ``hide_fn``: invoked once on a cache miss
    with measured verification, never on a hit — and the cached plan
    round-trips the measured value."""
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = CompressionConfig(comm_mode="auto", compressor="natural")
    params = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
    calls = []

    def omega_fn():
        calls.append(1)
        return tune.OmegaMeasurement(omega_hat=0.5, nmse=0.4,
                                     n_leaves=1, d_total=128)

    kw = dict(cache_dir=str(tmp_path), modes=("dense", "efbv"),
              link=tune.LinkModel.nominal(), verify_top=1,
              measure_fn=lambda c, t, k: 1e-3,
              analysis_fn=lambda: {"flops": 1e9, "bytes": 1e8},
              rates_fn=tune.DeviceRates.nominal, omega_fn=omega_fn)
    plan, hit = tune.autotune(comp, params, mesh, 2, **kw)
    assert not hit and len(calls) == 1
    assert plan.omega == pytest.approx(0.5)
    assert plan.omega_source == "measured"
    plan2, hit2 = tune.autotune(comp, params, mesh, 2, **kw)
    assert hit2 and len(calls) == 1       # a hit stays free of probe work
    assert plan2.omega == pytest.approx(0.5)
    assert plan2.omega_source == "measured"


def test_measure_omega_probe_matches_certificate():
    """The probe the trainer's ``--comm_mode auto`` path feeds the
    tuner: d-weighted like ``estimate_omega``, so the two are directly
    comparable (RandK's certificate is exact in expectation)."""
    like = {"a": jax.ShapeDtypeStruct((4, 1000), jnp.float32)}
    from repro.core.compressors import RandK

    m = tune.measure_omega(RandK(0.1), like, iters=4)
    assert m.source == "measured"
    assert m.n_leaves == 1 and m.d_total == 1000
    assert m.omega_hat == pytest.approx(
        tune.estimate_omega(RandK(0.1), like), rel=0.15)


def test_default_candidates_grid_and_filters():
    comp = CompressionConfig(compressor="topk",
                             compressor_kwargs=(("q", 0.25),))
    wtree = _wtree(jax.random.PRNGKey(0))
    cands = tune.default_candidates(comp, wtree)
    modes = {c.comm_mode for c in cands}
    assert "ef21" in modes  # contractive compressor -> ef21 searchable
    comp_u = CompressionConfig(compressor="natural")
    modes_u = {c.comm_mode for c in tune.default_candidates(comp_u, wtree)}
    assert "ef21" not in modes_u  # no contraction certificate, no ef21
    # efbv eta derives from the ESTIMATED omega (natural: omega=1/8)
    efbv = [c for c in tune.default_candidates(comp_u, wtree)
            if c.comm_mode == "efbv"]
    assert efbv and efbv[0].efbv_eta == pytest.approx(1.0 / (1.0 + 0.125))
    with pytest.raises(ValueError, match="carrier_pigeon"):
        tune.default_candidates(comp, wtree, modes=("carrier_pigeon",))


def test_search_plan_wire_grids_cross_product():
    """Wire grids cross the comm-mode grid: the search can pick a
    DIFFERENT codec per wire, the plan records the winning flags, and
    the per-wire bytes show up in the candidates' wire_bytes charge."""
    from repro.comm import build_transport
    from repro.configs import get_smoke_config

    cfg = get_smoke_config("qwen2-moe-a2.7b").with_(dtype="float32")
    comp = CompressionConfig(comm_mode="auto", moe_wire="q8", act_wire="q8")
    traffic = build_transport(
        CompressionConfig(comm_mode="dense", shift_rule="diana",
                          moe_wire="q8", act_wire="q8"),
        cfg, None, w=4, tokens_per_worker=64,
    ).extra_traffic()
    mesh = make_mesh((1, 1), ("data", "model"))
    wtree = _wtree(jax.random.PRNGKey(0), w=4)
    plan = tune.search_plan(
        comp, wtree, mesh, 4, modes=("dense", "randk_shared"),
        randk_grid=(0.05,), link=tune.LinkModel.nominal(), verify_top=0,
        moe_wire_grid=("none", "q8"), act_wire_grid=("none", "q8"),
        wire_traffic=traffic,
    )
    rows = plan.candidates
    # 2 modes x 2 moe flags x 2 act flags
    assert len(rows) == 8
    assert {(r["moe_wire"], r["act_wire"]) for r in rows} == {
        ("none", "none"), ("none", "q8"), ("q8", "none"), ("q8", "q8")}
    # q8 wires strictly beat identity-width wires on a bandwidth link
    by = {(r["comm_mode"], r["moe_wire"], r["act_wire"]):
          r["predicted_step_s"] for r in rows}
    assert by[("randk_shared", "q8", "q8")] < by[("randk_shared", "none",
                                                  "none")]
    assert (plan.moe_wire, plan.act_wire) == ("q8", "q8")


def test_autotune_cache_hit_skips_search(tmp_path):
    mesh = make_mesh((1, 1), ("data", "model"))
    comp = CompressionConfig(comm_mode="auto")
    params = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32),
              "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    calls = []

    def counting_measure(c, t, k):
        calls.append(c.label)
        return 1e-3

    kw = dict(cache_dir=str(tmp_path), modes=("dense", "randk_shared"),
              link=tune.LinkModel.nominal(), verify_top=2,
              measure_fn=counting_measure)
    plan, hit = tune.autotune(comp, params, mesh, 2, **kw)
    assert not hit and len(calls) == 2
    assert os.path.exists(tune.cache_path(str(tmp_path), plan.fingerprint))
    plan2, hit2 = tune.autotune(comp, params, mesh, 2, **kw)
    assert hit2 and len(calls) == 2  # no re-measure on the hit
    assert plan2 == plan
    _, hit3 = tune.autotune(comp, params, mesh, 2, force=True, **kw)
    assert not hit3 and len(calls) == 4  # --autotune forces a re-search


# ---------------------------------------------------------------------------
# auto comm-mode plumbing
# ---------------------------------------------------------------------------


def test_auto_mode_must_be_resolved_before_channels():
    comp = CompressionConfig(comm_mode="auto")
    with pytest.raises(ValueError, match="auto"):
        _ = comp.aggregation_mode
    with pytest.raises(ValueError, match="repro.tune|resolve"):
        make_channel(comp)
    with pytest.raises(ValueError, match="resolve"):
        make_channel("auto")
    resolved = tune.apply_plan(comp, _plan(mode="q8_ring"))
    assert resolved.comm_mode == "q8_ring"
    assert isinstance(make_channel(resolved), MeshChannel)


def test_apply_plan_sets_every_searched_knob():
    comp = CompressionConfig(comm_mode="auto")
    plan = _plan(mode="q8_ring_overlap", overlap_bucket_bytes=123456,
                 randk_q=0.02, q8_block_rows=32, efbv_eta=0.5, efbv_nu=0.9,
                 moe_wire="q8", act_wire="dense")
    r = tune.apply_plan(comp, plan)
    assert (r.comm_mode, r.overlap_bucket_bytes, r.randk_q,
            r.q8_block_rows, r.efbv_eta, r.efbv_nu) == (
        "q8_ring_overlap", 123456, 0.02, 32, 0.5, 0.9)
    assert (r.moe_wire, r.act_wire) == ("q8", "dense")
    ch = make_channel(r)
    assert ch.bucket_bytes == 123456 and ch.q8_block_rows == 32


def test_make_channel_plumbs_q8_block_rows():
    ch = make_channel("q8_ring_fused", q8_block_rows=32)
    assert isinstance(ch, MeshChannel) and ch.q8_block_rows == 32


def test_autotune_flag_requires_auto_mode():
    """--autotune/--tune_plan with an explicit concrete --comm_mode must
    refuse instead of silently replacing the requested mode."""
    from repro.launch.train import main

    with pytest.raises(SystemExit, match="comm_mode auto"):
        main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "1",
              "--batch", "1", "--seq", "8", "--comm_mode", "q8_ring",
              "--autotune"])


def test_disabled_config_with_auto_mode_is_dense():
    """A disabled CompressionConfig never resolves through the tuner:
    its transport is the dense mean (--no-compression --comm_mode auto
    must not trip the unresolved-auto guard)."""
    comp = CompressionConfig(enabled=False, comm_mode="auto")
    assert comp.aggregation_mode == "dense"
    ch = make_channel(comp)
    assert isinstance(ch, MeshChannel) and ch.mode == "dense"


# ---------------------------------------------------------------------------
# Drift resync (satellite): bounded h_bar drift over lossy aggregation
# ---------------------------------------------------------------------------


def _drift(h, h_bar):
    exact = jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), h)
    sq = jax.tree_util.tree_map(
        lambda e, b: jnp.sum((e - b) ** 2), exact, h_bar
    )
    return float(jnp.sqrt(sum(jax.tree_util.tree_leaves(sq))))


def _run_drift(steps, every, w=4, seed=0):
    """DIANA rounds over the LOSSY randk_shared aggregation: workers
    integrate their exact messages while h_bar tracks the sparsified
    aggregate — the ROADMAP's shift-tracking random walk."""
    key = jax.random.PRNGKey(seed)
    rule = DianaShift(alpha=0.5)
    q = NaturalCompression()
    ch = MeshChannel(mode="randk_shared", randk_q=0.1)
    like = _wtree(key, w=w)
    h = rule.init(like)
    h_bar = rule.init_bar(like)
    drifts = []
    for step in range(steps):
        k = jax.random.fold_in(key, 1000 + step)
        grads = jax.tree_util.tree_map(
            lambda a: jax.random.normal(jax.random.fold_in(k, 7), a.shape),
            like,
        )
        _, h, h_bar, _ = rule.round(q, k, grads, h, h_bar, channel=ch)
        h_bar = resync_h_bar(h, h_bar, jnp.int32(step), every)
        drifts.append(_drift(h, h_bar))
    return drifts


def test_resync_h_bar_unit():
    key = jax.random.PRNGKey(3)
    h = {"x": jax.random.normal(key, (4, 6))}
    h_bar = {"x": jax.random.normal(jax.random.fold_in(key, 1), (6,))}
    # non-firing step: untouched; firing step: the exact worker mean
    same = resync_h_bar(h, h_bar, jnp.int32(0), 5)
    np.testing.assert_array_equal(np.asarray(same["x"]),
                                  np.asarray(h_bar["x"]))
    fired = resync_h_bar(h, h_bar, jnp.int32(4), 5)
    np.testing.assert_allclose(np.asarray(fired["x"]),
                               np.asarray(h["x"]).mean(0), rtol=1e-6)
    # disabled / stateless: no-ops
    assert resync_h_bar(h, h_bar, jnp.int32(4), 0) is h_bar
    assert resync_h_bar(None, None, jnp.int32(4), 5) is None


def test_h_bar_drift_bounded_by_resync():
    """Over many lossy rounds the un-resynced drift RANDOM-WALKS away;
    with drift_resync_every=N it is pinned to ~0 at every resync and its
    running maximum stays bounded by the free-walk's."""
    steps, every = 40, 5
    free = _run_drift(steps, every=0)
    pinned = _run_drift(steps, every=every)
    assert free[-1] > 0.0  # the walk is real (lossy aggregation)
    # at every firing step the drift collapses to numerical zero
    fire_vals = [pinned[s] for s in range(every - 1, steps, every)]
    assert max(fire_vals) < 1e-4 * max(max(free), 1.0)
    # and the pinned walk never exceeds the free walk's excursion
    assert max(pinned) <= max(free) + 1e-9
    # the tail comparison: resync keeps the end-state drift strictly
    # below the free walk's end-state drift
    assert pinned[-1] < free[-1]


def test_train_step_resyncs_h_bar_from_worker_shifts():
    """drift_resync_every wired through the PRODUCTION train step: after
    a firing step the state's h_bar equals the exact worker mean of its
    shifts, where the unsynced run has drifted away."""
    from repro.configs import get_smoke_config
    from repro.configs.base import TrainConfig
    from repro.data.tokens import TokenStream
    from repro.launch.mesh import make_host_mesh, n_workers
    from repro.launch.train import build_train_step, init_state

    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    outs = {}
    for every in (0, 3):
        comp = CompressionConfig(
            enabled=True, compressor="natural", shift_rule="diana",
            comm_mode="randk_shared", drift_resync_every=every,
        )
        tcfg = TrainConfig(learning_rate=1e-2, total_steps=3,
                           warmup_steps=1, compression=comp)
        mesh = make_host_mesh()
        w = n_workers(mesh)
        state = init_state(jax.random.PRNGKey(0), cfg, tcfg, w)
        step = jax.jit(build_train_step(cfg, tcfg, mesh, w))
        stream = TokenStream(cfg, 32, 4)
        for i in range(3):  # steps 0,1,2 -> step 2 fires (2 % 3 == 2)
            state, _ = step(state, stream.batch(i))
        outs[every] = _drift(state.h, state.h_bar)
    assert outs[3] < 1e-5          # resynced: h_bar == mean(h)
    assert outs[0] > outs[3]       # un-resynced run really had drifted


# ---------------------------------------------------------------------------
# --comm_mode auto end-to-end (the acceptance path): tuner emits a plan
# JSON, train consumes it, the second invocation is a fingerprint hit
# ---------------------------------------------------------------------------


_AUTO_CLI = textwrap.dedent("""
    import os, glob, io, contextlib
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.launch.train import main

    cache = os.path.join("{tmp}", "tune_cache")
    args = ["--arch", "qwen3-0.6b", "--smoke", "--steps", "2",
            "--batch", "8", "--seq", "32",
            "--comm_mode", "auto", "--tune_cache", cache,
            # tiny measured grid: no interpret-mode Pallas on this path
            "--tune_modes", "dense,randk_shared,q8_ring"]

    buf1 = io.StringIO()
    with contextlib.redirect_stdout(buf1):
        state1 = main(args)
    out1 = buf1.getvalue()
    assert "tune: searched" in out1, out1
    assert "comm_mode=" in out1, out1
    assert np.isfinite(float(state1.bits)) and float(state1.bits) >= 0

    plans = glob.glob(os.path.join(cache, "tuneplan_*.json"))
    assert len(plans) == 1, plans  # the tuner emitted ONE TunePlan JSON
    import json
    plan = json.load(open(plans[0]))
    measured = [c for c in plan["candidates"]
                if c["measured_step_s"] is not None]
    assert len(measured) >= 1 and any(c["chosen"] for c in plan["candidates"])

    buf2 = io.StringIO()
    with contextlib.redirect_stdout(buf2):
        state2 = main(args)
    out2 = buf2.getvalue()
    assert "tune: cache hit" in out2, out2  # fingerprint hit, no re-search
    assert len(glob.glob(os.path.join(cache, "tuneplan_*.json"))) == 1
    print("AUTO_CLI_OK")
""")


def test_train_cli_auto_mode_8dev_subprocess(tmp_path):
    """--comm_mode auto end-to-end through the train CLI on 8 fake
    devices: search + plan JSON on the first run, fingerprint cache hit
    on the second."""
    r = subprocess.run(
        [sys.executable, "-c", _AUTO_CLI.format(tmp=str(tmp_path))],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=_REPO_ROOT,
    )
    assert "AUTO_CLI_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]