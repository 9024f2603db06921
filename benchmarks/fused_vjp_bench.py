"""Fused-backward-encode benchmark: step time + peak-HBM proxy.

Runs the REAL train step (``launch/train.build_train_step``) on the
qwen3-0.6b smoke config over 8 fake devices in a subprocess (process
isolation, like the overlap bench) for three comm modes:

  dense              no compression (the baseline the paper beats)
  q8_ring_overlap    post-hoc encode: dense backward, then the bucketed
                     AsyncChannel encodes + reduces each bucket
  q8_ring_fused_vjp  backward-fused encode: each layer's message is
                     emitted AS its cotangent (``repro.comm.fused_vjp``),
                     per-leaf buckets, no standalone encode stage

For each mode it records the median wall-clock step time, the final
loss, the per-round uplink bits the trainer accounted, and a peak-HBM
proxy from the compiled step's ``memory_analysis()`` (temp + argument
bytes — the quantity the fused path shrinks by never materialising the
dense message tree between backward and encode).  Writes the
machine-readable ``BENCH_fused_vjp.json`` next to the repo root.

NOTE on CPU numbers: interpret-mode Pallas makes absolute times
unrepresentative; the portable signals are the memory proxy, the bits
accounting, and fused-vs-overlap step-time RATIO (both run the same
kernels — the delta is the deleted standalone encode stage).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import REPO_ROOT as REPO, print_table, write_bench_json

STEPS = 5
OUT_JSON = "BENCH_fused_vjp.json"

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import time

import jax

from repro.configs import get_smoke_config
from repro.configs.base import CompressionConfig, TrainConfig
from repro.data.tokens import TokenStream
from repro.launch.train import build_train_step, init_state

steps = {steps}
batch, seq = 8, {seq}
cfg = get_smoke_config("qwen3-0.6b").with_(dtype="float32")
from repro.launch.mesh import make_mesh
mesh = make_mesh((8, 1), ("data", "model"))
w = 8

results = {{}}
for mode in ("dense", "q8_ring_overlap", "q8_ring_fused_vjp"):
    comp = CompressionConfig(comm_mode=mode, shift_rule="diana",
                             compressor="natural",
                             overlap_bucket_bytes=256 << 10)
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=steps,
                       compression=comp)
    state = init_state(jax.random.PRNGKey(0), cfg, tcfg, w)
    step_fn = jax.jit(build_train_step(cfg, tcfg, mesh, w))
    stream = TokenStream(cfg, seq, batch)
    compiled = step_fn.lower(state, stream.batch(0)).compile()
    mem = {{}}
    try:
        ma = compiled.memory_analysis()
        for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                mem[f] = int(v)
    except Exception:
        pass
    state, m = step_fn(state, stream.batch(0))  # warm
    jax.block_until_ready(m["loss"])
    bits0 = float(state.bits)
    times = []
    for i in range(1, steps + 1):
        t0 = time.perf_counter()
        state, m = step_fn(state, stream.batch(i))
        jax.block_until_ready(m["loss"])
        times.append(time.perf_counter() - t0)
    times.sort()
    results[mode] = {{
        "step_time_s": times[len(times) // 2],
        "final_loss": float(m["loss"]),
        "uplink_bits_per_round": (float(state.bits) - bits0) / steps,
        "peak_hbm_proxy_bytes": sum(mem.values()) if mem else None,
        "memory_analysis": mem,
    }}
print("BENCH_JSON " + json.dumps(results))
"""


def main(steps: int = STEPS, smoke: bool = False):
    steps = max(2, 2 if smoke else steps)
    seq = 32 if smoke else 64
    r = subprocess.run(
        [sys.executable, "-c", _CHILD.format(steps=steps, seq=seq)],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("BENCH_JSON ")),
        None,
    )
    if line is None:
        raise RuntimeError(
            f"fused_vjp bench child failed:\n{r.stdout}\n{r.stderr[-3000:]}"
        )
    results = json.loads(line[len("BENCH_JSON "):])
    write_bench_json(OUT_JSON, results)
    rows = [
        (
            mode,
            f"{m['step_time_s'] * 1e3:.1f}ms",
            f"{m['final_loss']:.4f}",
            f"{m['uplink_bits_per_round'] / 8e6:.3f}MB",
            (f"{m['peak_hbm_proxy_bytes'] / 1e6:.1f}MB"
             if m.get("peak_hbm_proxy_bytes") else "n/a"),
        )
        for mode, m in results.items()
    ]
    print_table(
        "Fused backward encode: real train step over 8 fake devices "
        "(interpret-mode kernels on CPU; memory proxy + bits are the "
        "portable signals)",
        ["mode", "step", "loss", "uplink/round", "HBM proxy"],
        rows,
    )
    return results


if __name__ == "__main__":
    main()
