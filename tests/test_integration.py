"""End-to-end integration: checkpoint/resume determinism of the full
TrainState, and sharded-vs-unsharded loss equivalence (the distributed
forward must compute the SAME numbers as the single-device one)."""

import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore, save
from repro.configs import get_smoke_config
from repro.configs.base import CompressionConfig, TrainConfig
from repro.data.tokens import TokenStream
from repro.launch.mesh import make_host_mesh, n_workers
from repro.launch.train import TrainState, build_train_step, init_state

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_checkpoint_resume_exact():
    """Train 6 steps; OR train 3, checkpoint the FULL TrainState (params,
    opt moments, DIANA shifts, PRNG key), restore, train 3 more — the
    loss trajectories must be bit-identical."""
    cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=6, warmup_steps=1,
                       compression=CompressionConfig(
                           compressor="natural", shift_rule="diana"))
    mesh = make_host_mesh()
    w = n_workers(mesh)
    step = jax.jit(build_train_step(cfg, tcfg, mesh, w))
    stream = TokenStream(cfg, 32, 4)

    # straight run
    st = init_state(jax.random.PRNGKey(0), cfg, tcfg, w)
    losses_a = []
    for i in range(6):
        st, m = step(st, stream.batch(i))
        losses_a.append(float(m["loss"]))

    # checkpointed run
    st = init_state(jax.random.PRNGKey(0), cfg, tcfg, w)
    losses_b = []
    for i in range(3):
        st, m = step(st, stream.batch(i))
        losses_b.append(float(m["loss"]))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "state.npz")
        save(path, st._asdict(), step=3)
        like = jax.tree_util.tree_map(jnp.zeros_like, st._asdict())
        st2 = TrainState(**restore(path, like))
    for i in range(3, 6):
        st2, m = step(st2, stream.batch(i))
        losses_b.append(float(m["loss"]))

    np.testing.assert_array_equal(losses_a, losses_b)


_SHARDED_LOSS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.dist import params_pspecs, validate_pspecs
    from repro.launch.mesh import make_mesh
    from repro.models import model as M

    for arch in ("qwen3-0.6b", "qwen2-moe-a2.7b"):
        cfg = get_smoke_config(arch).with_(
            dtype="float32", d_model=256, n_heads=4, n_kv_heads=4,
            vocab_size=512,
        )
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks}
        loss_ref, _ = M.train_loss(params, cfg, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        specs = validate_pspecs(params, params_pspecs(params), mesh)
        sharded = jax.device_put(
            params, jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                                 is_leaf=lambda x: isinstance(x, P)))
        sb = jax.device_put(batch, NamedSharding(mesh, P("data")))
        with jax.sharding.set_mesh(mesh):
            loss_sh, _ = jax.jit(
                lambda p, b: M.train_loss(p, cfg, b))(sharded, sb)
        err = abs(float(loss_ref) - float(loss_sh))
        assert err < 5e-4, (arch, float(loss_ref), float(loss_sh))
    print("SHARDED_LOSS_OK")
""")


def test_sharded_loss_matches_single_device():
    """The 8-fake-device sharded forward computes the same loss as the
    single-device one (GSPMD partitioning preserves the math)."""
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_LOSS],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=_REPO_ROOT,
    )
    assert "SHARDED_LOSS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
