"""Compile the main-path q8 Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's refusals:
block shapes off the (8, 128) tiling, scalar stores to VMEM, too much
VMEM.  These tests lower with ``interpret=False`` for a v5e:2x2 topology
that is described, not attached, at qwen3-0.6b's real leaf widths, and
check that the compiled HLO carries the kernels (``tpu_custom_call``);
the 4-chip DIANA train step (smoke widths) must compile with them too.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and collection must give every
pytest worker the same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.configs.base import CompressionConfig, TrainConfig
from repro.data.tokens import TokenStream
from repro.dist.collectives import q8_ring_tree_mean
from repro.kernels.q8ring.kernel import (
    LANE,
    q8_dequant_add_2d,
    q8_quantize_2d,
    q8_quantize_chunk_3d,
)
from repro.kernels.q8ring.ops import FusedQ8, q8_layout, ring_chunk_layout
from repro.launch.mesh import make_host_mesh, n_workers
from repro.launch.train import (
    batch_pspecs,
    init_state,
    jit_train_step,
    named_shardings,
    state_pspecs,
)

#: qwen3-0.6b leaf sizes: qk_norm scale, the d_model norms, an MLP
#: projection, and the (tied) embedding
QWEN3_LEAVES = {
    "qk_norm": 128,
    "norm": 1024,
    "mlp": 1024 * 3072,
    "embed": 151936 * 1024,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    """The trainer's ("data", "model") = (4, 1) mesh on the described chips."""
    return make_host_mesh(topo.devices)


def _compiled_hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("leaf", sorted(QWEN3_LEAVES))
def test_q8_quantize_and_dequant_compile(one_chip, leaf):
    rows, block, rows_pad = q8_layout(QWEN3_LEAVES[leaf])
    x = jax.ShapeDtypeStruct((rows_pad, LANE), jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((rows_pad, LANE), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((rows_pad // block, 1), jnp.float32,
                             sharding=one_chip)
    enc = _compiled_hlo(
        lambda a, u: q8_quantize_2d(a, u, block_rows=block, interpret=False),
        x, x,
    )
    dec = _compiled_hlo(
        lambda q_, s_, acc: q8_dequant_add_2d(q_, s_, acc, block_rows=block,
                                              interpret=False),
        q, s, x,
    )
    assert "tpu_custom_call" in enc
    assert "tpu_custom_call" in dec


@pytest.mark.parametrize("leaf", ["qk_norm", "norm", "mlp", "embed"])
def test_q8_ring_chunk_compiles_at_four_way(one_chip, leaf):
    n = 4
    rows_c, block = ring_chunk_layout(QWEN3_LEAVES[leaf], n)
    chunks = jax.ShapeDtypeStruct((n, rows_c, LANE), jnp.float32,
                                  sharding=one_chip)
    u = jax.ShapeDtypeStruct((rows_c, LANE), jnp.float32, sharding=one_chip)
    cid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    hlo = _compiled_hlo(
        lambda c, u_, i: q8_quantize_chunk_3d(c, u_, i, block_rows=block,
                                              interpret=False),
        chunks, u, cid,
    )
    q = jax.ShapeDtypeStruct((rows_c, LANE), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((rows_c // block, 1), jnp.float32,
                             sharding=one_chip)
    add = _compiled_hlo(
        lambda q_, s_, acc: q8_dequant_add_2d(q_, s_, acc, block_rows=block,
                                              interpret=False),
        q, s, u,
    )
    assert "tpu_custom_call" in hlo
    assert "tpu_custom_call" in add


def test_fused_ring_tree_mean_compiles_on_2x2(mesh_2x2):
    mesh = mesh_2x2
    rows = NamedSharding(mesh, P("data"))
    tree = {
        "w": jax.ShapeDtypeStruct((4, 256, 512), jnp.float32, sharding=rows),
        "qk_norm": jax.ShapeDtypeStruct((4, 128), jnp.float32, sharding=rows),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    hlo = _compiled_hlo(
        lambda k, t: q8_ring_tree_mean(k, t, mesh,
                                       codec=FusedQ8(interpret=False)),
        key, tree,
    )
    assert "collective-permute" in hlo
    assert "tpu_custom_call" in hlo


def _placed(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )


@pytest.mark.parametrize("mode",
                         ["dense", "q8_ring_overlap", "q8_ring_fused_vjp"])
def test_train_step_compiles_on_2x2(mesh_2x2, monkeypatch, mode):
    """The jitted DIANA step, placed as ``train.main`` places it (W = 4,
    state and batch in their mesh layout), with every q8 kernel compiled:
    GSPMD must never be asked to partition a Mosaic kernel."""
    # the codec resolves interpret from the backend, which is the CPU here
    monkeypatch.setattr(FusedQ8, "run_interpret", property(lambda _: False))
    cfg = get_smoke_config("qwen3-0.6b")
    comp = CompressionConfig(compressor="q8_block", shift_rule="diana",
                             comm_mode=mode)
    tcfg = TrainConfig(learning_rate=3e-4, total_steps=3, warmup_steps=1,
                       compression=comp)
    mesh = mesh_2x2
    w = n_workers(mesh)
    shapes = jax.eval_shape(lambda k: init_state(k, cfg, tcfg, w),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    state_sh = named_shardings(state_pspecs(shapes, mesh, tcfg), mesh)
    batch = jax.eval_shape(lambda: TokenStream(cfg, 32, 8).batch(0))
    batch_sh = named_shardings(batch_pspecs(batch, mesh), mesh)
    hlo = (jit_train_step(cfg, tcfg, mesh, w, state_sh)
           .lower(_placed(shapes, state_sh), _placed(batch, batch_sh))
           .compile().as_text())
    assert "tpu_custom_call" in hlo
    if mode != "dense":
        assert "collective-permute" in hlo
