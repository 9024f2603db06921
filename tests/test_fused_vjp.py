"""Fused backward-encode tests: THE CONTRACT — the fused-VJP path
(messages emitted as cotangents, ``repro.comm.fused_vjp``) is BITWISE
identical to the post-hoc encode path, per shift rule x channel.

Three layers of pinning, mirroring tests/test_overlap.py:

  * unit: the per-worker tag body vmaps to exactly ``message_leaf``,
    the key derivation reproduces ``Channel.shift_round``'s, and
    ``jax.grad`` through ``message_tag`` emits the message;
  * round: ``fused_round`` == ``shift_round`` bitwise on SimChannel,
    MeshChannel and the drained AsyncChannel, for every fusible rule,
    including the f32 bits counter;
  * end-to-end: the full train step (8 fake devices, subprocess) —
    ``q8_ring_fused_vjp`` reproduces ``q8_ring_overlap``'s TrainState
    bitwise, plus awkward shapes on an ODD world size (5 devices).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    AsyncChannel,
    FUSED_VJP_MODES,
    SimChannel,
    check_fusible,
    encode_on_backward,
    fused_message_bits,
    make_channel,
    message_tag,
    plan_buckets,
    round_message_keys,
    worker_keys,
)
from repro.comm.wire import leaf_key
from repro.core.compressors import make_compressor
from repro.core.shift_rules import make_shift_rule

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every fusible registered rule (dcgd is FixedShift under a second name)
FUSIBLE_RULES = ("fixed", "dcgd", "diana", "ef21", "efbv")


def _rule(name):
    if name == "diana":
        return make_shift_rule("diana", alpha=0.125,
                               c=make_compressor("natural"))
    return make_shift_rule(name)


def _wtree(key, w=4):
    # awkward on purpose: scalar-per-worker leaf, non-lane-divisible dims
    return {
        "a": jax.random.normal(key, (w, 40)),
        "b": {
            "c": jax.random.normal(jax.random.fold_in(key, 1), (w, 3, 5)),
            "d": jax.random.normal(jax.random.fold_in(key, 2), (w,)),
        },
        "e": jax.random.normal(jax.random.fold_in(key, 3), (w, 7)),
    }


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)
        ),
        a, b,
    )


def _fused_msgs(rule, q, key, wtree, h, w):
    """Emulate what the fused backward emits: vmap the tag's per-worker
    body over the pre-derived round keys (the value contract)."""
    params_like = jax.tree_util.tree_map(lambda x: x[0], wtree)
    keys = round_message_keys(rule, q, key, params_like, w)
    leaves, treedef = jax.tree_util.tree_flatten(wtree)
    h_leaves = ([None] * len(leaves) if h is None
                else jax.tree_util.tree_leaves(h))
    out = []
    for lk, g, hl in zip(keys, leaves, h_leaves):
        if hl is None:
            m = jax.vmap(
                lambda kk, gg: rule.message_leaf_worker(q, kk, gg, None)
            )(lk, g)
        else:
            m = jax.vmap(
                lambda kk, gg, hv: rule.message_leaf_worker(q, kk, gg, hv)
            )(lk, g, hl)
        out.append(m)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Unit: keys, values, bits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule_name", FUSIBLE_RULES)
def test_worker_body_vmaps_to_message_leaf(rule_name):
    """VALUES: vmapped ``message_leaf_worker`` over ``message_keys`` is
    bitwise the post-hoc ``message_leaf``, and ``message_bits_aot``
    equals its live bits — per leaf, including scalar leaves."""
    rule, q = _rule(rule_name), make_compressor("natural")
    key = jax.random.PRNGKey(3)
    w = 4
    wtree = _wtree(key, w)
    h = rule.init(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), wtree
    ))
    leaves = jax.tree_util.tree_leaves(wtree)
    h_leaves = ([None] * len(leaves) if h is None
                else jax.tree_util.tree_leaves(h))
    for i, (g, hl) in enumerate(zip(leaves, h_leaves)):
        lk = leaf_key(key, i)
        ref_m, ref_bits = rule.message_leaf(q, lk, g, hl)
        wkeys = rule.message_keys(q, lk, w)
        if hl is None:
            got = jax.vmap(
                lambda kk, gg: rule.message_leaf_worker(q, kk, gg, None)
            )(wkeys, g)
        else:
            got = jax.vmap(
                lambda kk, gg, hv: rule.message_leaf_worker(q, kk, gg, hv)
            )(wkeys, g, hl)
        np.testing.assert_array_equal(np.asarray(ref_m), np.asarray(got))
        assert float(ref_bits) == rule.message_bits_aot(q, g)


def test_round_message_keys_match_shift_round_derivation():
    """KEYS: the pre-derived fused keys are exactly the post-hoc
    derivation — round key's first 3-split row, folded to each leaf's
    GLOBAL position, then the codec's worker derivation."""
    q = make_compressor("natural")
    rule = _rule("fixed")
    key = jax.random.PRNGKey(9)
    w = 4
    params = {"a": jnp.zeros((40,)), "b": {"c": jnp.zeros((3, 5))}}
    keys = round_message_keys(rule, q, key, params, w)
    k_msg = jax.random.split(key, 3)[0]
    assert len(keys) == 2
    for i, k in enumerate(keys):
        np.testing.assert_array_equal(
            np.asarray(k), np.asarray(worker_keys(q, leaf_key(k_msg, i), w))
        )


def test_message_tag_grad_emits_message():
    """``jax.grad`` through a tagged loss yields
    ``message_leaf_worker`` of the dense cotangent — the tag really
    rewrites the backward, not the value."""
    q = make_compressor("natural")
    rule = _rule("fixed")
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (13,))
    cot = jax.random.normal(jax.random.fold_in(key, 1), (13,))
    wkeys = rule.message_keys(q, key, 1)
    k0 = jax.tree_util.tree_map(lambda k: k[0], wkeys)

    def loss(p):
        return jnp.vdot(cot, message_tag(rule, q, p, k0, None))

    assert float(loss(x)) == float(jnp.vdot(cot, x))  # forward: identity
    g = jax.grad(loss)(x)
    ref = rule.message_leaf_worker(q, k0, cot, None)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(ref))


def test_encode_on_backward_grad_is_message_tree():
    """Tree-level: grad of a tapped synthetic loss == the vmapped
    message tree the fused round consumes (params value unchanged)."""
    q = make_compressor("natural")
    w = 3
    key = jax.random.PRNGKey(7)
    params = {"a": jax.random.normal(key, (11,)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (2, 3))}
    wcot = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 2), (w, *p.shape)),
        params,
    )
    for rule_name in ("fixed", "diana"):
        rule = _rule(rule_name)
        keys = round_message_keys(rule, q, key, params, w)

        def one_worker(cot, kt):
            def loss(p):
                tapped = encode_on_backward(rule, q, p, kt, None)
                return sum(
                    jnp.vdot(c, t)
                    for c, t in zip(jax.tree_util.tree_leaves(cot),
                                    jax.tree_util.tree_leaves(tapped))
                )
            return jax.grad(loss)(params)

        got = jax.vmap(one_worker)(wcot, keys)
        ref = _fused_msgs(rule, q, key, wcot, None, w)
        _assert_trees_equal(got, ref)


def test_fused_message_bits_matches_round_bits():
    q = make_compressor("natural")
    rule = _rule("diana")
    wtree = _wtree(jax.random.PRNGKey(0))
    total = fused_message_bits(rule, q, wtree)
    assert total == sum(
        rule.message_bits_aot(q, leaf)
        for leaf in jax.tree_util.tree_leaves(wtree)
    )
    assert total > 0


# ---------------------------------------------------------------------------
# Fusibility gate
# ---------------------------------------------------------------------------


def test_check_fusible_accepts_all_fusible_rules():
    for name in FUSIBLE_RULES:
        check_fusible(_rule(name))  # must not raise


def test_check_fusible_rejects_dense_grad_rules():
    from repro.core.iterate_comp import VRGDCI

    bad = [
        make_shift_rule("star", c=make_compressor("natural")),
        make_shift_rule("rand_diana"),
        VRGDCI(),
    ]
    for rule in bad:
        with pytest.raises(ValueError, match="not fusible"):
            check_fusible(rule)


def test_train_step_rejects_non_fusible_config():
    """The trainer refuses rule x fused-mode combos at BUILD time."""
    from repro.configs import get_smoke_config
    from repro.configs.base import CompressionConfig, TrainConfig
    from repro.launch.train import build_train_step

    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    for rule_name, match in (("rand_diana", "not fusible"),
                             ("vr_gdci", "no gradient message")):
        comp = CompressionConfig(comm_mode="q8_ring_fused_vjp",
                                 shift_rule=rule_name)
        tcfg = TrainConfig(learning_rate=1e-3, total_steps=1,
                           compression=comp)
        with pytest.raises(ValueError, match=match):
            build_train_step(cfg, tcfg, None, 1)


def test_encode_on_backward_validates_key_count():
    q = make_compressor("natural")
    rule = _rule("fixed")
    params = {"a": jnp.zeros((3,)), "b": jnp.zeros((4,))}
    keys = round_message_keys(rule, q, jax.random.PRNGKey(0),
                              {"a": jnp.zeros((3,))}, 2)
    with pytest.raises(ValueError, match="leaf"):
        encode_on_backward(rule, q, params, keys, None)


# ---------------------------------------------------------------------------
# Per-leaf bucket plan
# ---------------------------------------------------------------------------


def test_plan_buckets_per_leaf():
    """per_leaf plans give every leaf its own bucket, in the same
    reverse-layer order as the byte-budget plan — the property that
    makes fused-vs-overlap bits accumulation order identical."""
    wtree = _wtree(jax.random.PRNGKey(0))
    plan = plan_buckets(wtree, 1 << 30, per_leaf=True)
    assert len(plan) == plan.n_leaves
    assert [b.indices for b in plan.buckets] == [
        (i,) for i in reversed(range(plan.n_leaves))
    ]


def test_make_channel_fused_mode_is_per_leaf_async():
    ch = make_channel("q8_ring_fused_vjp")
    assert isinstance(ch, AsyncChannel)
    assert ch.per_leaf and ch.mode == "q8_ring_fused"
    from repro.configs.base import CompressionConfig

    cfg = CompressionConfig(comm_mode="q8_ring_fused_vjp")
    assert cfg.aggregation_mode == "q8_ring_fused"
    assert make_channel(cfg).per_leaf


# ---------------------------------------------------------------------------
# Round-level contract: fused_round == shift_round, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule_name", FUSIBLE_RULES)
def test_fused_round_bitexact_sim_and_async(rule_name):
    """``fused_round`` on the emitted message tree reproduces
    ``shift_round`` on the dense tree BITWISE — outputs, new shifts,
    and the f32 bits counter — on SimChannel and the drained
    AsyncChannel across bucket granularities."""
    rule, q = _rule(rule_name), make_compressor("natural")
    key = jax.random.PRNGKey(21)
    w = 4
    wtree = _wtree(key, w)
    wlike = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), wtree
    )
    h0, hb0 = rule.init(wlike), rule.init_bar(wlike)
    msgs = _fused_msgs(rule, q, key, wtree, h0, w)

    channels = [SimChannel(),
                AsyncChannel(mode="dense", bucket_bytes=64),
                AsyncChannel(mode="dense", bucket_bytes=1 << 30)]
    for ch in channels:
        ref = ch.shift_round(rule, q, key, wtree, h0, hb0)
        got = ch.fused_round(rule, q, key, msgs, h0, hb0)
        _assert_trees_equal(ref[:3], got[:3])
        assert float(ref[3]) == float(got[3]), (rule_name, type(ch).__name__)


def test_fused_round_rejects_non_fusible_rule():
    rule = make_shift_rule("rand_diana")
    q = make_compressor("natural")
    wtree = _wtree(jax.random.PRNGKey(0))
    wlike = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), wtree
    )
    h, hb = rule.init(wlike), rule.init_bar(wlike)
    for ch in (SimChannel(), AsyncChannel(mode="dense", bucket_bytes=64)):
        with pytest.raises(ValueError, match="not fusible"):
            ch.fused_round(rule, q, jax.random.PRNGKey(0), wtree, h, hb)


# ---------------------------------------------------------------------------
# End-to-end: the full train step, 8 fake devices (subprocess)
# ---------------------------------------------------------------------------


_E2E = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import get_smoke_config
    from repro.configs.base import CompressionConfig, TrainConfig
    from repro.data.tokens import TokenStream
    from repro.launch.train import build_train_step, init_state

    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8, 1), ("data", "model"))
    w, batch, seq, steps = 8, 8, 32, 2

    states = {}
    for mode in ("q8_ring_overlap", "q8_ring_fused_vjp"):
        comp = CompressionConfig(comm_mode=mode, shift_rule="diana",
                                 compressor="natural",
                                 overlap_bucket_bytes=256 << 10)
        tcfg = TrainConfig(learning_rate=1e-3, total_steps=steps,
                           compression=comp)
        state = init_state(jax.random.PRNGKey(0), cfg, tcfg, w)
        step_fn = jax.jit(build_train_step(cfg, tcfg, mesh, w))
        stream = TokenStream(cfg, seq, batch)
        for i in range(steps):
            state, m = step_fn(state, stream.batch(i))
        jax.block_until_ready(m["loss"])
        states[mode] = state

    a, b = states["q8_ring_overlap"], states["q8_ring_fused_vjp"]
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)),
        (a.params, a.h, a.h_bar), (b.params, b.h, b.h_bar))
    assert float(a.bits) == float(b.bits), (float(a.bits), float(b.bits))
    print("FUSED_E2E_OK")
""")


def test_train_step_fused_bitexact_vs_overlap_8dev_subprocess():
    """THE CONTRACT end-to-end: the fused train step reproduces the
    post-hoc overlap step's TrainState (params, shifts, h_bar, bits)
    bitwise over 2 real steps on 8 fake devices."""
    r = subprocess.run(
        [sys.executable, "-c", _E2E],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=_REPO_ROOT,
    )
    assert "FUSED_E2E_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


_AWKWARD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=5"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.comm import AsyncChannel
    from repro.comm.fused_vjp import round_message_keys
    from repro.core.compressors import make_compressor
    from repro.core.shift_rules import make_shift_rule

    # odd world size; leaf sizes not divisible by lanes or world size;
    # a scalar-per-worker leaf — mirrors tests/test_overlap.py
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((5,), ("data",))
    key = jax.random.PRNGKey(0)
    w = 5
    tree = {"a": jax.random.normal(key, (w, 777)),
            "s": jax.random.normal(jax.random.fold_in(key, 1), (w,)),
            "m": jax.random.normal(jax.random.fold_in(key, 2), (w, 13, 3))}
    tree = jax.device_put(tree, NamedSharding(mesh, P("data")))

    q = make_compressor("natural")
    rule = make_shift_rule("diana", alpha=0.125,
                           c=make_compressor("natural"))
    wlike = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         tree)
    h0, hb0 = rule.init(wlike), rule.init_bar(wlike)

    params_like = jax.tree.map(lambda x: x[0], tree)
    keys = round_message_keys(rule, q, key, params_like, w)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h_leaves = jax.tree_util.tree_leaves(h0)
    msgs = jax.tree_util.tree_unflatten(treedef, [
        jax.vmap(lambda kk, gg, hv: rule.message_leaf_worker(q, kk, gg, hv))(
            lk, g, hl)
        for lk, g, hl in zip(keys, leaves, h_leaves)
    ])

    post = AsyncChannel(mode="dense", mesh=mesh, bucket_bytes=1024)
    fused = AsyncChannel(mode="dense", mesh=mesh, bucket_bytes=1024,
                         per_leaf=True)
    ref = jax.jit(lambda k, t: post.shift_round(rule, q, k, t, h0, hb0))(
        key, tree)
    got = jax.jit(lambda k, t: fused.fused_round(rule, q, k, t, h0, hb0))(
        key, msgs)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)),
        ref[:3], got[:3])
    assert float(ref[3]) == float(got[3])
    print("FUSED_AWKWARD_OK")
""")


def test_fused_round_awkward_shapes_odd_workers_subprocess():
    """Awkward shapes on an ODD world size (5): per-leaf fused round ==
    byte-bucketed post-hoc round, bitwise, through a real mesh."""
    r = subprocess.run(
        [sys.executable, "-c", _AWKWARD],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=_REPO_ROOT,
    )
    assert "FUSED_AWKWARD_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


_FUSED_CLI = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.launch.train import main
    state = main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "2",
                  "--batch", "8", "--seq", "32",
                  "--compressor", "natural", "--comm_mode",
                  "q8_ring_fused_vjp"])
    assert np.isfinite(float(state.bits)) and float(state.bits) > 0
    print("FUSED_CLI_OK")
""")


def test_train_cli_fused_vjp_8dev_subprocess():
    """--comm_mode q8_ring_fused_vjp end-to-end through the train CLI
    on 8 fake devices (the acceptance path for the fused runtime)."""
    assert "q8_ring_fused_vjp" in FUSED_VJP_MODES
    r = subprocess.run(
        [sys.executable, "-c", _FUSED_CLI],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=_REPO_ROOT,
    )
    assert "FUSED_CLI_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
