"""``correct`` comes out false where it must, on the CPU at a smoke size.

* The control: the reference computed with float8 matrix products, put
  in the system's place, fails the cell's limits.
* Faults planted in the system underneath a whole run of the harness
  (past its look for a chip): a step that returns its state unchanged,
  half of the batch left out, the reported loss altered where it is
  produced, and (four workers, in a subprocess with four host devices)
  the exchange between chips left out.
* The entry point exits non-zero with no result where there is no TPU,
  and in a directory that holds only the benchmark's own files.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import run  # noqa: E402
import tokens as TK  # noqa: E402
from reference import model as RM  # noqa: E402
from reference import train as RT  # noqa: E402

ONE = "qwen3-0.6b.dp1.q8-diana"
FOUR = "qwen3-0.6b.dp4.q8ring-overlap"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell(workload: str) -> run.Cell:
    """The workload at a smoke size, in the configuration's own bf16."""
    cell = run.load_cell(workload)
    cell.config["model"].update(n_layers=2, d_model=128, n_heads=4,
                                n_kv_heads=2, head_dim=32, d_ff=256,
                                vocab_size=512)
    cell.traffic.update(batch=2 * cell.traffic["workers"], seq=128)
    return cell


def test_control_fails_the_limits():
    cell = tiny_cell(ONE)
    t, n = cell.traffic, run.COMPARED_STEPS
    key = run.seed_key(21)
    params0 = RM.init_params(jax.random.fold_in(key, 0), cell.model)
    batches = [np.asarray(TK.batch(21, i, t["batch"], t["seq"], 512))
               for i in range(n)]
    skey = jax.random.fold_in(key, 1)
    ref = RT.Reference(cell.model, t).run(params0, batches, skey, n)
    ctl = RT.Reference(cell.model, t, precision="fp8").run(
        params0, batches, skey, n)
    got = RT.gaps(ctl, ref)
    assert any(got[k] > cell.limits[k] for k in got), (got, cell.limits)


def _broken(fault):
    """A ``jit_train_step`` whose step carries ``fault``."""
    import repro.launch.train as T

    def jit_train_step(cfg, tcfg, mesh, w, sh, **kw):
        step = T.build_train_step(cfg, tcfg, mesh, w)

        def broken(state, batch):
            if fault == "half_batch":
                tok = batch["tokens"]
                half = tok.shape[0] // 2
                batch = dict(batch, tokens=jnp.concatenate([tok[:half]] * 2))
            new, met = step(state, batch)
            if fault == "unchanged":
                new = state
            if fault == "loss_altered":
                met = dict(met, loss=met["loss"] * 1.01)
            return new, met
        return jax.jit(broken, out_shardings=(sh, None), donate_argnums=0)
    return jit_train_step


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "loss_altered"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    import repro.launch.train as T

    monkeypatch.setattr(T, "jit_train_step", _broken(fault))
    result, _ = run.run_cell(tiny_cell(ONE), 2**31 + 7, 0.5, False,
                             jax.devices()[:1], PEAKS)
    assert result["correct"] is False, result["checks"]


FOUR_DEVICE_SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
import repro.dist.collectives as C
import test_bench_control as B
import run
C._ring_allreduce_fused = lambda key, x, axis, n, codec: x * n
res, _ = run.run_cell(B.tiny_cell(B.FOUR), 2**31 + 9, 0.5, False,
                      jax.devices()[:4], B.PEAKS)
print(json.dumps({"correct": res["correct"], "checks": res["checks"]}))
"""


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICE_SCRIPT, str(HERE),
         str(ROOT / "src")], env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is False, res["checks"]


def _entry(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", ONE, "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_a_result():
    out = _entry(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_bare_benchmark_directory_exits_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("runs", "__pycache__"))
    out = _entry(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
