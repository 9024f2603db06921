"""Launch-layer integration: the production train step (all shift rules
and comm modes, routed through the Channel) trains a tiny LM on one
host; the EF21 comm mode also runs through the train CLI on 8 fake
devices."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import CompressionConfig, TrainConfig
from repro.data.tokens import TokenStream
from repro.launch.mesh import make_host_mesh, n_workers
from repro.launch.train import build_train_step, init_state

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(comp: CompressionConfig, steps=100, lr=1e-2):
    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    tcfg = TrainConfig(learning_rate=lr, total_steps=steps, warmup_steps=2,
                       compression=comp)
    mesh = make_host_mesh()
    w = n_workers(mesh)
    state = init_state(jax.random.PRNGKey(0), cfg, tcfg, w)
    step = jax.jit(build_train_step(cfg, tcfg, mesh, w))
    stream = TokenStream(cfg, 64, 4)
    losses = []
    for i in range(steps):
        state, metrics = step(state, stream.batch(i))
        losses.append(float(metrics["loss"]))
    return losses, state


@pytest.mark.parametrize("rule", ["fixed", "diana", "rand_diana", "efbv"])
def test_train_step_rules_learn(rule):
    losses, state = _train(CompressionConfig(
        enabled=True, compressor="natural", shift_rule=rule))
    assert np.isfinite(losses).all(), losses[-5:]
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02, (
        rule, losses[:3], losses[-3:])
    assert float(state.bits) > 0


def test_train_step_dense_baseline():
    losses, _ = _train(CompressionConfig(enabled=False))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02


def test_vr_gdci_trains():
    """Algorithm 2 (compressed iterates) on the LM — the model-broadcast
    direction of the paper."""
    losses, state = _train(
        CompressionConfig(enabled=True, compressor="natural",
                          shift_rule="vr_gdci", shift_alpha=0.5,
                          gdci_eta=0.9),
        steps=150, lr=0.2,   # RAW SGD direction: needs SGD-scale gamma
    )
    assert np.isfinite(losses).all()
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.015, (
        losses[:3], losses[-3:])


def test_ef21_comm_mode_trains():
    """The ef21 comm mode (error feedback with a contractive TopK codec)
    learns on the LM; comm_mode alone selects the rule."""
    losses, state = _train(CompressionConfig(
        enabled=True, compressor="topk", compressor_kwargs=(("q", 0.25),),
        comm_mode="ef21"))
    assert np.isfinite(losses).all(), losses[-5:]
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02, (
        losses[:3], losses[-3:])
    assert float(state.bits) > 0
    # shifts are live: EF21 integrates every message into h
    assert state.h is not None
    assert float(jnp.sum(jnp.abs(jax.tree_util.tree_leaves(state.h)[0]))) > 0


_EF21_CLI = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.launch.train import main
    state = main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "2",
                  "--batch", "8", "--seq", "32",
                  "--compressor", "topk", "--comm_mode", "ef21"])
    assert np.isfinite(float(state.bits)) and float(state.bits) > 0
    assert state.h is not None  # EF21 shift state allocated (8 workers)
    import jax
    assert jax.tree_util.tree_leaves(state.h)[0].shape[0] == 8
    print("EF21_CLI_OK")
""")


def test_train_cli_ef21_8dev_subprocess():
    """--comm_mode ef21 end-to-end through the train CLI on 8 fake
    devices (the acceptance path for the error-feedback comm mode)."""
    r = subprocess.run(
        [sys.executable, "-c", _EF21_CLI],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=_REPO_ROOT,
    )
    assert "EF21_CLI_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_compile_cache_follows_env_and_stays_off_on_cpu(monkeypatch,
                                                         tmp_path):
    """The entry points' cache: JAX_COMPILATION_CACHE_DIR wins when set;
    otherwise the checkout path, except on the CPU backend, where the
    cache is left off and JAX's setting untouched."""
    from repro.launch.cache import use_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    was = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == was


def test_diana_matches_dense_direction():
    """With an Identity compressor, DIANA's estimator equals the plain
    mean gradient (g_bar = h_bar + mean(g - h)) — the launch path must be
    EXACTLY dense-SGD-equivalent then."""
    losses_id, _ = _train(CompressionConfig(
        enabled=True, compressor="identity", shift_rule="diana"), steps=40)
    losses_dn, _ = _train(CompressionConfig(enabled=False), steps=40)
    # f32 reassociation drifts slowly; exact up to accumulated rounding
    np.testing.assert_allclose(losses_id, losses_dn, rtol=2e-3, atol=2e-3)
