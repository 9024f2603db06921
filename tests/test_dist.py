"""Distribution substrate tests.  The multi-device collective paths run
in a SUBPROCESS with --xla_force_host_platform_device_count (the main
pytest process must keep 1 device for the smoke tests)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.collectives import dense_mean, randk_shared_mean
from repro.dist.worker_grads import per_worker_grads, split_batch
from repro.launch.mesh import make_mesh

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_split_batch_roundtrip():
    b = {"tokens": jnp.arange(24).reshape(12, 2)}
    wb = split_batch(b, 4)
    assert wb["tokens"].shape == (4, 3, 2)
    np.testing.assert_array_equal(
        np.asarray(wb["tokens"]).reshape(12, 2), np.asarray(b["tokens"])
    )


def test_per_worker_grads_match_full_grad():
    """mean_i grad_i == grad of the mean loss (sanity of the vmap path)."""
    w = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    batch = {"x": jnp.arange(8.0).reshape(8, 1), "y": jnp.arange(8.0)}

    def loss_fn(params, b):
        pred = (b["x"] * params["w"][0, 0] + params["w"][1, 1]).squeeze(-1)
        l = jnp.mean((pred - b["y"]) ** 2)
        return l, {"l": l}

    params = {"w": w}
    wbatch = split_batch(batch, 4)
    wg, loss, _ = per_worker_grads(loss_fn, params, wbatch)
    assert wg["w"].shape == (4, 2, 2)
    full, _ = jax.grad(loss_fn, has_aux=True)(params, batch)
    np.testing.assert_allclose(
        np.asarray(jnp.mean(wg["w"], 0)), np.asarray(full["w"]), rtol=1e-6
    )


def test_randk_shared_mean_unbiased():
    key = jax.random.PRNGKey(0)
    wtree = {"a": jax.random.normal(key, (6, 50))}
    true_mean = np.asarray(jnp.mean(wtree["a"], 0))
    acc = np.zeros(50)
    n = 600
    for i in range(n):
        out = randk_shared_mean(jax.random.PRNGKey(i), wtree, 0.2)
        acc += np.asarray(out["a"])
    np.testing.assert_allclose(acc / n, true_mean, atol=0.15)


def test_randk_shared_mean_sparsity():
    wtree = {"a": jnp.ones((4, 100))}
    out = randk_shared_mean(jax.random.PRNGKey(1), wtree, 0.1)
    nz = (np.asarray(out["a"]) != 0).sum()
    assert nz == 10  # exactly K coordinates survive


_RING_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.dist.collectives import q8_ring_tree_mean

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8, 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    w = 8
    tree = {"a": jax.random.normal(key, (w, 1000)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (w, 33))}
    tree = jax.device_put(tree, NamedSharding(mesh, P("data")))

    out = jax.jit(
        lambda k, t: q8_ring_tree_mean(k, t, mesh, worker_axes=("data",),
                                       pod_axis=None)
    )(key, tree)
    ref = jax.tree.map(lambda a: jnp.mean(a, 0), tree)
    for k in ("a", "b"):
        err = np.abs(np.asarray(out[k]) - np.asarray(ref[k])).max()
        scale = np.abs(np.asarray(ref[k])).max() + 1.0
        assert err < 0.05 * scale, (k, err, scale)
    print("RING_OK")
""")


def test_q8_ring_allreduce_subprocess():
    """int8 ring all-reduce ~= exact mean over 8 fake devices."""
    r = subprocess.run(
        [sys.executable, "-c", _RING_TEST],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=_REPO_ROOT,
    )
    assert "RING_OK" in r.stdout, r.stdout + r.stderr


_SHARDING_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import params_pspecs, validate_pspecs
    from repro.models import model as M
    from repro.configs import get_smoke_config

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    for arch in ("qwen3-0.6b", "qwen2-moe-a2.7b", "rwkv6-3b", "zamba2-1.2b"):
        cfg = get_smoke_config(arch)
        shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = validate_pspecs(shapes, params_pspecs(shapes), mesh)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        def check(leaf, sp):
            for size, ax in zip(leaf.shape, tuple(sp)):
                if ax is None: continue
                axs = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axs: n *= sizes[a]
                assert size % n == 0, (arch, leaf.shape, sp)
        jax.tree.map(check, shapes, specs,
                     is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    print("SPECS_OK")
""")


def test_param_specs_valid_on_mesh_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _SHARDING_TEST],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=_REPO_ROOT,
    )
    assert "SPECS_OK" in r.stdout, r.stdout + r.stderr


def test_compressed_tree_mean_dense_matches_dense_mean():
    """The identity/dense wire format is EXACTLY the plain mean — both
    via the comm-mode string and via CompressionConfig dispatch."""
    from repro.configs.base import CompressionConfig
    from repro.dist.collectives import compressed_tree_mean

    key = jax.random.PRNGKey(3)
    wtree = {
        "a": jax.random.normal(key, (4, 17)),
        "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (4, 3, 5))},
    }
    ref = dense_mean(wtree)
    outs = [
        compressed_tree_mean(wtree, "dense", key),
        compressed_tree_mean(
            wtree,
            CompressionConfig(enabled=True, compressor="identity",
                              comm_mode="dense"),
            key,
        ),
        # a disabled config is dense regardless of its comm_mode
        compressed_tree_mean(
            wtree, CompressionConfig(enabled=False, comm_mode="q8_ring"), key
        ),
    ]
    for out in outs:
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)
            ),
            out, ref,
        )


def test_worker_stacked_pspec_prepends_worker_axes():
    """worker_stacked_pspec = P(worker_axes, *params_pspecs entry) for
    EVERY parameter leaf, on both host and multi-pod meshes."""
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.dist.sharding import params_pspecs, worker_stacked_pspec
    from repro.models import model as M

    cfg = get_smoke_config("qwen3-0.6b")
    shapes = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    specs = params_pspecs(shapes)
    is_p = lambda x: isinstance(x, P)

    for mesh_shape, axes, lead in (
        ((1, 1), ("data", "model"), "data"),
        ((1, 1, 1), ("pod", "data", "model"), ("pod", "data")),
    ):
        mesh = make_mesh(mesh_shape, axes)
        wspecs = jax.tree_util.tree_map(
            lambda sp: worker_stacked_pspec(mesh, sp), specs, is_leaf=is_p
        )

        def check(sp, wsp):
            assert tuple(wsp)[0] == lead, (sp, wsp)
            assert tuple(wsp)[1:] == tuple(sp), (sp, wsp)

        jax.tree_util.tree_map(check, specs, wspecs, is_leaf=is_p)
