"""The plain references against the system at smoke sizes, on the CPU.

The references (``bench/reference/``) import nothing of the system; here
the test does, to show that both compute the same thing: loss and
gradients of both architectures, the q8 codec against the kernels' own
oracle (``kernels/q8ring/ref.py``), and whole DIANA + AdamW steps of a
tiny cell through the harness against the reference's.
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402
from reference import model as RM  # noqa: E402
from reference import train as RT  # noqa: E402

DENSE = {"arch_type": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
         "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
         "qk_norm": True, "rope_theta": 1e6, "norm_eps": 1e-5,
         "tie_embeddings": True, "dtype": "float32"}
HYBRID = {"arch_type": "hybrid", "n_layers": 4, "d_model": 64, "n_heads": 4,
          "n_kv_heads": 4, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
          "ssm_state": 16, "attn_every": 2, "rwkv_head_dim": 16,
          "conv_kernel": 4, "norm_eps": 1e-5, "rope_theta": 1e4,
          "qk_norm": False, "tie_embeddings": False, "dtype": "float32"}
ARCH = {"dense": "qwen3-0.6b", "hybrid": "zamba2-1.2b"}


def _system_cfg(m):
    from repro.configs import get_config

    fields = {k: v for k, v in m.items() if not k.startswith("mamba_")}
    return get_config(ARCH[m["arch_type"]]).with_(**fields)


@pytest.mark.parametrize("m,seq", [(DENSE, 48), (HYBRID, 64)],
                         ids=["dense", "hybrid"])
def test_reference_loss_and_grads_match_the_system(m, seq):
    from repro.models import model as M

    m = dict(m, mamba_expand=2, mamba_head_dim=m.get("rwkv_head_dim", 0))
    cfg = _system_cfg(m)
    key = jax.random.PRNGKey(3)
    params = RM.init_params(key, m)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (2, seq), 0,
                              m["vocab_size"])
    sys_l, sys_g = jax.value_and_grad(
        lambda p: M.train_loss(p, cfg, {"tokens": toks})[0])(params)
    with jax.default_matmul_precision("highest"):
        ref_l, ref_g = jax.value_and_grad(
            lambda p: jnp.mean(RM.row_losses(p, toks, m, RM.Matmul())))(params)
    assert float(sys_l) == pytest.approx(float(ref_l), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(sys_g),
                    jax.tree_util.tree_leaves(ref_g)):
        scale = float(jnp.linalg.norm(b)) + 1e-12
        assert float(jnp.linalg.norm(a - b)) / scale < 1e-3


def test_q8_roundtrip_matches_the_kernel_oracle():
    from repro.kernels.q8ring.ref import q8_dequant_add_ref, q8_quantize_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (300, 200)) * 0.01
    key = jax.random.PRNGKey(7)
    d = x.size
    rows = -(-d // 128)
    block = min(64, rows)
    rows_pad = -(-rows // block) * block
    flat = jnp.pad(x.ravel(), (0, rows_pad * 128 - d)).reshape(rows_pad, 128)
    u = jax.random.uniform(key, (rows_pad, 128))
    q, s = q8_quantize_ref(flat, u, block=block)
    want = q8_dequant_add_ref(q, s, jnp.zeros_like(flat), block=block)
    got = RT.q8_roundtrip(x, key, 64)
    np.testing.assert_array_equal(np.asarray(got).ravel(),
                                  np.asarray(want).ravel()[:d])


def test_reference_precisions_differ_only_in_matmuls():
    m = dict(DENSE)
    key = jax.random.PRNGKey(1)
    params = RM.init_params(key, m)
    toks = jax.random.randint(key, (1, 16), 0, m["vocab_size"])
    f32 = RM.row_losses(params, toks, m, RM.Matmul("float32"))
    fp8 = RM.row_losses(params, toks, m, RM.Matmul("fp8"))
    assert jnp.all(jnp.isfinite(fp8))
    assert 0 < abs(float(f32[0] - fp8[0])) < 0.05 * abs(float(f32[0]))
    with pytest.raises(ValueError):
        RM.Matmul("int4")


def tiny_cell(workload: str, dtype: str = "float32") -> run.Cell:
    """The workload's cell cut to a smoke size that the CPU runs."""
    cell = run.load_cell(workload)
    cell.config["model"].update(n_layers=2, d_model=64, n_heads=4,
                                n_kv_heads=2, head_dim=16, d_ff=128,
                                vocab_size=256, dtype=dtype)
    cell.traffic.update(batch=2 * cell.traffic["workers"], seq=32)
    return cell


def test_diana_adamw_steps_match_the_reference():
    cell = tiny_cell("qwen3-0.6b.dp1.q8-diana")
    dev = jax.devices()[:1]
    sys_ = run.build_system(cell, dev, 5)
    key = run.seed_key(5)
    state = sys_.init(key)
    compiled = sys_.step.lower(state, run.place_batch(sys_, 0)).compile()
    n = run.COMPARED_STEPS
    _, losses, obs = run.compared_steps(sys_, compiled, state, n, key)
    prog = run.readings(sys_, losses, obs)
    import tokens as TK

    batches = [np.asarray(TK.batch(5, i, 2, 32, 256)) for i in range(n)]
    params0 = RM.init_params(jax.random.fold_in(key, 0), cell.model)
    ref = RT.Reference(cell.model, cell.traffic).run(
        params0, batches, jax.random.fold_in(key, 1), n)
    got = RT.gaps(prog, ref)
    assert got["loss_gap"] < 1e-5
    assert got["grad_gap"] < 1e-3
    assert got["change_gap"] < 1e-3
    for i in range(n):
        np.testing.assert_array_equal(
            batches[i], np.asarray(sys_.stream.batch(i)["tokens"]))
