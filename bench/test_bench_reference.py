"""The plain references against the system at smoke sizes, on the CPU.

The references (``bench/reference/``) import nothing of the system; here
the test does, to show that both compute the same thing: loss and
gradients of every architecture in ``bench/reference/archs/`` (each
module brings its own smoke model and the system's arch id), the q8
codec against the kernels' own oracle (``kernels/q8ring/ref.py``), and
whole DIANA + AdamW steps of a tiny cell through the harness against
the reference's.  The architecture lookup is shown to take a new module
as a new file, and the counts behind ``mfu`` and ``q8_roofline`` are
pinned to the float.
"""

from __future__ import annotations

import dataclasses
import math
import pathlib
import shutil
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import counts  # noqa: E402
import run  # noqa: E402
from reference import model as RM  # noqa: E402
from reference import train as RT  # noqa: E402

ARCHS = sorted(p.stem for p in RM.ARCHS.glob("*.py"))


def smoke(arch_type: str) -> dict:
    """The smoke model of the architecture module ``arch_type``."""
    return {**RM.arch(arch_type).SMOKE, "arch_type": arch_type}


def _system_cfg(arch_type: str, m: dict):
    from repro.configs import get_config

    cfg = get_config(RM.arch(arch_type).SYSTEM_ARCH)
    known = {f.name for f in dataclasses.fields(cfg)}
    return cfg.with_(**{k: v for k, v in m.items() if k in known})


@pytest.mark.parametrize("arch_type", ARCHS)
def test_reference_loss_and_grads_match_the_system(arch_type):
    from repro.models import model as M

    m = smoke(arch_type)
    seq = RM.arch(arch_type).SMOKE_SEQ
    cfg = _system_cfg(arch_type, m)
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


def test_a_new_architecture_is_a_new_file(tmp_path, monkeypatch):
    """A copy of ``dense.py`` under another name, in an ``archs``
    directory of its own, is found, initialised, run and counted."""
    for name in ARCHS:
        shutil.copy(RM.ARCHS / f"{name}.py", tmp_path)
    shutil.copy(RM.ARCHS / "dense.py", tmp_path / "dense_copy.py")
    monkeypatch.setattr(RM, "ARCHS", tmp_path)
    m, copy = smoke("dense"), smoke("dense_copy")
    assert copy == {**m, "arch_type": "dense_copy"}
    key = jax.random.PRNGKey(2)
    params = RM.init_params(key, copy)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(RM.init_params(key, m))):
        np.testing.assert_array_equal(a, b)
    toks = jax.random.randint(key, (2, 16), 0, m["vocab_size"])
    np.testing.assert_array_equal(
        RM.row_losses(params, toks, copy, RM.Matmul()),
        RM.row_losses(params, toks, m, RM.Matmul()))
    assert counts.model_flops_per_token(copy, 1024) == \
        counts.model_flops_per_token(m, 1024)


@pytest.mark.parametrize("entry", ["init_params", "row_losses", "flops"])
def test_an_unknown_architecture_names_the_missing_file(entry):
    m = {**smoke("dense"), "arch_type": "no_such_arch"}
    key = jax.random.PRNGKey(0)
    call = {
        "init_params": lambda: RM.init_params(key, m),
        "row_losses": lambda: RM.row_losses(
            RM.init_params(key, smoke("dense")), jnp.zeros((1, 4), int), m,
            RM.Matmul()),
        "flops": lambda: counts.model_flops_per_token(m, 1024),
    }[entry]
    with pytest.raises(ValueError, match=r"archs/no_such_arch\.py"):
        call()


def _qwen3_cell_model() -> dict:
    return run.load_cell("qwen3-0.6b.dp1.q8-diana").model


#: the floats these counts gave when each architecture was a branch of
#: ``counts.py``: the numerators of ``mfu`` and ``q8_roofline`` must not
#: move when the code behind them does
PINNED_FLOPS = [
    (_qwen3_cell_model, 1024, 3928571904.0),
    (lambda: smoke("hybrid"), 256, 1759680.0),
]


@pytest.mark.parametrize("model,seq,want", PINNED_FLOPS,
                         ids=["qwen3-0.6b", "hybrid-smoke"])
def test_model_flops_per_token_are_pinned(model, seq, want):
    assert counts.model_flops_per_token(model(), seq) == want


@pytest.mark.parametrize("chips,want", [(1, 3576881624.0),
                                        (4, 13561518276.0)])
def test_q8_bytes_per_step_of_qwen3_are_pinned(chips, want):
    m = _qwen3_cell_model()
    leaves = [(int(math.prod(a.shape)), a.dtype.itemsize)
              for a in jax.tree_util.tree_leaves(jax.eval_shape(
                  lambda k: RM.init_params(k, m), jax.random.PRNGKey(0)))]
    assert counts.q8_bytes_per_step(leaves, 1, chips, 64) == want


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
    m = smoke("dense")
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
