"""Autotuner benchmark: predicted vs measured step time per comm mode.

Runs the ``repro.tune`` pipeline on a synthetic worker-stacked gradient
tree over 8 fake devices (subprocess, like the dist tests — the parent
process must keep its single device): calibrate the alpha-beta link
model by timed micro-reduces, predict each candidate mode's step time
from the structural wire model, then MEASURE every candidate through
its real channel and mark the plan the tuner picks.  The artifact is
the tuner's trust record: if predicted ranking and measured ranking
drift apart run over run, the cost model is rotting.

Writes the machine-readable ``BENCH_autotune.json`` next to the repo
root (uploaded as a CI artifact alongside ``BENCH_overlap.json`` /
``BENCH_efbv.json``).

NOTE on CPU numbers: fake-device collectives share one memory bus, so
alpha dominates and the measured ranking mostly reflects launch/dispatch
structure, not TPU link speed — predicted-vs-measured AGREEMENT per
mode is the portable signal, and the fused overlap mode runs
interpret-mode Pallas (keep the tree tiny in smoke).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import REPO_ROOT as REPO, print_table, write_bench_json

ITERS = 5
OUT_JSON = "BENCH_autotune.json"

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.tune import (
    Candidate, calibrate_link, compose_step_s, measure_candidate,
    predict_step,
)

iters = {iters}
smoke = {smoke}
from repro.launch.mesh import make_mesh
mesh = make_mesh((8, 1), ("data", "model"))
key = jax.random.PRNGKey(0)
w = 8

# synthetic reverse-layer gradient stack (kept modest so the fused
# overlap candidate's interpret-mode Pallas stays benchmarkable on CPU)
dims = [(256, 256), (256, 512), (512,), (256, 256), (64, 256), (333,)]
if smoke:
    dims = dims[:4]
tree = {{
    f"layer{{i:02d}}": jax.random.normal(jax.random.fold_in(key, i), (w, *d))
    for i, d in enumerate(dims)
}}
tree = jax.device_put(tree, NamedSharding(mesh, P("data")))

bucket = 256 << 10   # tiny bucket: the synthetic tree is ~1 MB/worker
candidates = [
    Candidate("dense"),
    Candidate("randk_shared", randk_q=0.05),
    Candidate("q8_ring"),
    Candidate("q8_ring_overlap", bucket_bytes=bucket),
]

link = calibrate_link(mesh, tree, iters=iters)
rows = {{}}
best, best_t = None, float("inf")
for c in candidates:
    pred = predict_step(c, tree, link, w)
    comm_s = measure_candidate(c, mesh, tree, key, iters=iters)
    step_s = compose_step_s(pred.compute_s, comm_s, c.overlap)
    rows[c.label] = {{
        "comm_mode": c.comm_mode,
        "predicted_step_s": pred.step_s,
        "measured_step_s": step_s,
        "wire_bytes": pred.wire_bytes,
        "n_buckets": pred.n_buckets,
        "chosen": False,
    }}
    if step_s < best_t:
        best, best_t = c.label, step_s
rows[best]["chosen"] = True
rows["_link"] = {{"alpha_s": link.alpha_s,
                  "beta_s_per_byte": link.beta_s_per_byte}}
print("BENCH_JSON " + json.dumps(rows))
"""


def main(iters: int = ITERS, smoke: bool = False):
    iters = max(2, iters)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD.format(iters=iters, smoke=smoke)],
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
            f"autotune bench child failed:\n{r.stdout}\n{r.stderr[-3000:]}"
        )
    results = json.loads(line[len("BENCH_JSON "):])
    write_bench_json(OUT_JSON, results)
    rows = [
        (
            label,
            f"{m['predicted_step_s'] * 1e3:.2f}ms",
            f"{m['measured_step_s'] * 1e3:.2f}ms",
            f"{m['wire_bytes'] / 1e6:.3f}MB",
            m["n_buckets"],
            "<- chosen" if m["chosen"] else "",
        )
        for label, m in results.items() if not label.startswith("_")
    ]
    print_table(
        "Autotuner: predicted vs measured step time over 8 fake devices "
        "(CPU: alpha-dominated; agreement per mode is the signal)",
        ["candidate", "predicted", "measured", "wire/worker", "buckets", ""],
        rows,
    )
    return results


if __name__ == "__main__":
    main()
