"""Overlap runtime tests: the reverse-layer bucketer, the AsyncChannel
start/finish protocol, and THE CONTRACT — drained synchronously the
AsyncChannel is bit-exact with MeshChannel in the same aggregation mode
(q8_ring over 8 fake devices runs in a subprocess, like the dist
tests).  Plus the comm-mode validation satellites."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    AGGREGATION_MODES,
    AsyncChannel,
    MeshChannel,
    SimChannel,
    make_channel,
    plan_buckets,
)
from repro.comm.overlap import Handle, Inflight
from repro.configs.base import CompressionConfig
from repro.core.compressors import NaturalCompression, RandK

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wtree(key, w=4):
    return {
        "a": jax.random.normal(key, (w, 40)),
        "b": {
            "c": jax.random.normal(jax.random.fold_in(key, 1), (w, 3, 5)),
            "d": jax.random.normal(jax.random.fold_in(key, 2), (w,)),
        },
        "e": jax.random.normal(jax.random.fold_in(key, 3), (w, 7)),
    }


# ---------------------------------------------------------------------------
# Bucketer
# ---------------------------------------------------------------------------


def test_plan_buckets_reverse_order_and_coverage():
    """Buckets walk leaves LAST first (reverse-layer order: what makes
    overlap with backward compute possible), cover every leaf exactly
    once, and respect the byte budget for multi-leaf buckets."""
    wtree = _wtree(jax.random.PRNGKey(0))
    budget = 64  # bytes: d (4) + c (60) fit; a (160) and e (28) split off
    plan = plan_buckets(wtree, budget)
    flat_order = [i for b in plan.buckets for i in b.indices]
    assert sorted(flat_order) == list(range(plan.n_leaves))
    assert flat_order == sorted(flat_order, reverse=True)  # reverse-layer
    for b in plan.buckets:
        if len(b.indices) > 1:
            assert b.nbytes <= budget


def test_plan_buckets_oversize_leaf_gets_own_bucket():
    """Leaves are never split: one above-budget leaf = one bucket."""
    wtree = {"big": jnp.zeros((2, 1000)), "small": jnp.zeros((2, 2))}
    plan = plan_buckets(wtree, 16)
    assert [b.indices for b in plan.buckets] == [(1,), (0,)]
    assert plan.buckets[1].nbytes == 4000


def test_plan_buckets_single_bucket_when_budget_large():
    wtree = _wtree(jax.random.PRNGKey(0))
    plan = plan_buckets(wtree, 1 << 30)
    assert len(plan) == 1
    assert plan.buckets[0].indices == tuple(reversed(range(plan.n_leaves)))


def test_plan_buckets_aot_from_shapes():
    """Plans are buildable from eval_shape trees (no data movement)."""
    wtree = _wtree(jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), wtree
    )
    assert plan_buckets(shapes, 64) == plan_buckets(wtree, 64)


def test_plan_buckets_rejects_bad_budget():
    with pytest.raises(ValueError, match="bucket_bytes"):
        plan_buckets(_wtree(jax.random.PRNGKey(0)), 0)


# ---------------------------------------------------------------------------
# AsyncChannel: dense-mode contract on one device + the handle protocol
# ---------------------------------------------------------------------------


def test_async_channel_dense_bit_exact_vs_mesh():
    """Every Channel op, bit-exact against MeshChannel("dense") across
    bucket granularities — bucketing must change scheduling, not math."""
    key = jax.random.PRNGKey(11)
    wtree = _wtree(key)
    mesh_ch = MeshChannel(mode="dense")
    for q in (NaturalCompression(), RandK(0.5)):
        for budget in (1, 64, 1 << 30):
            a = AsyncChannel(mode="dense", bucket_bytes=budget)
            m_m, bar_m, b_m = mesh_ch.push_mean(q, key, wtree)
            m_a, bar_a, b_a = a.push_mean(q, key, wtree)
            jax.tree_util.tree_map(
                lambda x, y: np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y)
                ),
                (m_m, bar_m), (m_a, bar_a),
            )
            assert float(b_m) == float(b_a)


def test_async_channel_uplink_matches_base_channel():
    key = jax.random.PRNGKey(12)
    wtree = _wtree(key)
    q = NaturalCompression()
    m_s, b_s = SimChannel().uplink(q, key, wtree)
    m_a, b_a = AsyncChannel(mode="dense", bucket_bytes=64).uplink(q, key, wtree)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)
        ),
        m_s, m_a,
    )
    assert float(b_s) == float(b_a)


def test_async_channel_handles_finish_any_order():
    """reduce_start issues one handle per bucket; reordered handles
    still reassemble the exact tree, and a dropped handle raises."""
    key = jax.random.PRNGKey(13)
    wtree = _wtree(key)
    ch = AsyncChannel(mode="dense", bucket_bytes=64)
    inflight = ch.reduce_start(key, wtree)
    assert len(inflight.handles) == len(plan_buckets(wtree, 64))
    assert all(isinstance(h, Handle) for h in inflight.handles)
    ref = ch.finish(inflight)
    shuffled = Inflight(
        inflight.treedef, inflight.n_leaves, tuple(inflight.handles[::-1])
    )
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)
        ),
        ref, ch.finish(shuffled),
    )
    partial = Inflight(
        inflight.treedef, inflight.n_leaves, tuple(inflight.handles[:-1])
    )
    with pytest.raises(ValueError, match="handles cover"):
        ch.finish(partial)


def test_async_channel_rejects_bad_config():
    with pytest.raises(ValueError, match="aggregation mode"):
        AsyncChannel(mode="carrier_pigeon")
    # a bad bucket budget fails at CONSTRUCTION, not in the first
    # jitted collective — and an explicit 0 is an error, not the default
    with pytest.raises(ValueError, match="bucket_bytes"):
        AsyncChannel(mode="dense", bucket_bytes=0)
    with pytest.raises(ValueError, match="bucket_bytes"):
        make_channel("q8_ring_overlap", bucket_bytes=-4096)
    # a bucket budget on a non-overlap channel would be silently
    # ignored — reject the meaningless combination at construction
    with pytest.raises(ValueError, match="bucket_bytes"):
        make_channel("q8_ring", bucket_bytes=1 << 20)


# ---------------------------------------------------------------------------
# comm-mode plumbing (satellites)
# ---------------------------------------------------------------------------


def test_make_channel_overlap_mode_and_config():
    ch = make_channel("q8_ring_overlap")
    assert isinstance(ch, AsyncChannel) and ch.mode == "q8_ring_fused"
    cfg = CompressionConfig(comm_mode="q8_ring_overlap",
                            overlap_bucket_bytes=12345)
    assert cfg.aggregation_mode == "q8_ring_fused"
    assert cfg.effective_shift_rule == "diana"  # overlap is transport-only
    ch = make_channel(cfg)
    assert isinstance(ch, AsyncChannel) and ch.bucket_bytes == 12345


def test_make_channel_sim_uniform_for_string_and_config():
    """'sim' selects the parameter-server channel whether it arrives as
    a mode string or inside a CompressionConfig (regression: the config
    path used to slip past the sim branch into MeshChannel validation)."""
    assert isinstance(make_channel("sim"), SimChannel)
    assert isinstance(
        make_channel(CompressionConfig(comm_mode="sim")), SimChannel
    )


def test_make_channel_rejects_unknown_mode_listing_modes():
    """A typo'd comm mode must fail AT CONSTRUCTION with the accepted
    modes in the message, not as a confusing downstream failure."""
    for bad in ("q8ring", "carrier_pigeon"):
        with pytest.raises(ValueError) as ei:
            make_channel(bad)
        for m in AGGREGATION_MODES:
            assert m in str(ei.value)
        assert "q8_ring_overlap" in str(ei.value)
    with pytest.raises(ValueError) as ei:
        make_channel(CompressionConfig(comm_mode="q8ring"))
    assert "q8ring" in str(ei.value)


def test_compressed_tree_mean_rejects_unknown_mode_listing_modes():
    from repro.dist.collectives import compressed_tree_mean

    with pytest.raises(ValueError) as ei:
        compressed_tree_mean({"a": jnp.ones((2, 4))}, "q8ring",
                             jax.random.PRNGKey(0))
    for m in AGGREGATION_MODES:
        assert m in str(ei.value)


# ---------------------------------------------------------------------------
# THE CONTRACT on the q8 ring + fused-ring accuracy (8 fake devices)
# ---------------------------------------------------------------------------


_CONTRACT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.comm import AsyncChannel, MeshChannel
    from repro.core.compressors import NaturalCompression

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8, 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    w = 8
    tree = {"a": jax.random.normal(key, (w, 1000)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (w, 33)),
            "c": jax.random.normal(jax.random.fold_in(key, 2), (w,))}
    tree = jax.device_put(tree, NamedSharding(mesh, P("data")))

    mch = MeshChannel(mode="q8_ring", mesh=mesh)
    ach = AsyncChannel(mode="q8_ring", mesh=mesh, bucket_bytes=512)
    assert len(ach.reduce_start(key, tree).handles) > 1  # really bucketed

    # drained sync == MeshChannel, bit-exact
    rm = jax.jit(mch.reduce_mean)(key, tree)
    ra = jax.jit(ach.reduce_mean)(key, tree)
    jax.tree_util.tree_map(
        lambda p, q: np.testing.assert_array_equal(np.asarray(p),
                                                   np.asarray(q)), rm, ra)

    # the composed overlapped round too (messages, aggregate, bits)
    q = NaturalCompression()
    mm, rm2, bm = jax.jit(lambda k, t: mch.push_mean(q, k, t))(key, tree)
    ma, ra2, ba = jax.jit(lambda k, t: ach.push_mean(q, k, t))(key, tree)
    jax.tree_util.tree_map(
        lambda p, q_: np.testing.assert_array_equal(np.asarray(p),
                                                    np.asarray(q_)),
        (mm, rm2), (ma, ra2))
    assert float(bm) == float(ba)

    # the fused overlap mode stays within int8 tolerance of the exact mean
    ref = jax.tree.map(lambda a: jnp.mean(a, 0), tree)
    af = AsyncChannel(mode="q8_ring_fused", mesh=mesh, bucket_bytes=512)
    rf = jax.jit(af.reduce_mean)(key, tree)
    for k in tree:
        err = np.abs(np.asarray(rf[k]) - np.asarray(ref[k])).max()
        scale = np.abs(np.asarray(ref[k])).max() + 1.0
        assert err < 0.06 * scale, (k, err, scale)
    print("CONTRACT_OK")
""")


def test_async_channel_q8_ring_contract_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _CONTRACT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=_REPO_ROOT,
    )
    assert "CONTRACT_OK" in r.stdout, r.stdout + r.stderr[-3000:]


_AWKWARD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=5"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.compressors import Int8Stochastic
    from repro.dist.collectives import q8_ring_tree_mean
    from repro.kernels.q8ring.ops import FusedQ8

    # odd world size; leaf sizes not divisible by lanes or world size;
    # a scalar-per-worker leaf
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((5,), ("data",))
    key = jax.random.PRNGKey(0)
    w = 5
    tree = {"a": jax.random.normal(key, (w, 777)),
            "s": jax.random.normal(jax.random.fold_in(key, 1), (w,)),
            "m": jax.random.normal(jax.random.fold_in(key, 2), (w, 13, 3))}
    tree = jax.device_put(tree, NamedSharding(mesh, P("data")))
    ref = jax.tree.map(lambda a: jnp.mean(a, 0), tree)

    outs = {}
    for name, codec in (("unfused", Int8Stochastic()), ("fused", FusedQ8())):
        out = jax.jit(lambda k, t: q8_ring_tree_mean(
            k, t, mesh, worker_axes=("data",), pod_axis=None,
            codec=codec))(key, tree)
        outs[name] = out
        for k in tree:
            err = np.abs(np.asarray(out[k]) - np.asarray(ref[k])).max()
            scale = np.abs(np.asarray(ref[k])).max() + 1.0
            assert err < 0.06 * scale, (name, k, err, scale)
    # fused vs unfused agree within int8 quantization tolerance
    for k in tree:
        d = np.abs(np.asarray(outs["fused"][k])
                   - np.asarray(outs["unfused"][k])).max()
        scale = np.abs(np.asarray(ref[k])).max() + 1.0
        assert d < 0.1 * scale, (k, d, scale)
    print("AWKWARD_OK")
""")


def test_q8_ring_awkward_shapes_odd_workers_subprocess():
    """Satellite: fused vs unfused q8 ring on leaf sizes not divisible
    by the lane/world size, scalar leaves, and an ODD worker count."""
    r = subprocess.run(
        [sys.executable, "-c", _AWKWARD],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=_REPO_ROOT,
    )
    assert "AWKWARD_OK" in r.stdout, r.stdout + r.stderr[-3000:]


_OVERLAP_CLI = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.launch.train import main
    state = main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "2",
                  "--batch", "8", "--seq", "32",
                  "--compressor", "natural", "--comm_mode",
                  "q8_ring_overlap"])
    assert np.isfinite(float(state.bits)) and float(state.bits) > 0
    print("OVERLAP_CLI_OK")
""")


def test_train_cli_q8_ring_overlap_8dev_subprocess():
    """--comm_mode q8_ring_overlap end-to-end through the train CLI on 8
    fake devices (the acceptance path for the overlapped runtime)."""
    r = subprocess.run(
        [sys.executable, "-c", _OVERLAP_CLI],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=_REPO_ROOT,
    )
    assert "OVERLAP_CLI_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
