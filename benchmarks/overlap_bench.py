"""Overlap-runtime benchmark: step time + HLO collective bytes + buckets.

Compares the three aggregation paths on a synthetic worker-stacked
gradient tree over 8 fake devices (subprocess, like the dist tests —
the parent process must keep its single device):

  dense             plain psum mean (the no-compression baseline)
  q8_ring           MeshChannel over the generic Int8Stochastic ring
  q8_ring_overlap   AsyncChannel: reverse-layer buckets over the
                    Pallas-fused blockwise-int8 ring

For each mode it reports median wall-clock per reduce step, the
HLO-counted collective bytes of the jitted step (structural: the q8
payloads really appear as s8 on the wire), and the bucket count, and
writes the machine-readable ``BENCH_overlap.json`` next to the repo
root so the perf trajectory is tracked run over run.

NOTE on CPU numbers: the fused kernels run in Pallas interpret mode on
CPU, so *step time* here tracks scheduling structure, not TPU kernel
speed — bytes-on-wire and bucket structure are the portable signals.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import REPO_ROOT as REPO, print_table, write_bench_json

STEPS = 20
OUT_JSON = "BENCH_overlap.json"

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.comm import make_channel, plan_buckets
from repro.launch.hlo_stats import collective_bytes

steps = {steps}
smoke = {smoke}
from repro.launch.mesh import make_mesh
mesh = make_mesh((8, 1), ("data", "model"))
key = jax.random.PRNGKey(0)
w = 8

# synthetic reverse-layer gradient stack: a few transformer-ish leaves
# (kept modest so interpret-mode Pallas stays benchmarkable on CPU)
dims = [(256, 256), (256, 512), (512,), (256, 256), (64, 256), (333,)]
if smoke:
    dims = dims[:4]
tree = {{
    f"layer{{i:02d}}": jax.random.normal(jax.random.fold_in(key, i), (w, *d))
    for i, d in enumerate(dims)
}}
tree = jax.device_put(tree, NamedSharding(mesh, P("data")))
n_elem = sum(x.size // w for x in tree.values())

results = {{}}
for mode in ("dense", "q8_ring", "q8_ring_overlap"):
    kw = {{"bucket_bytes": 256 << 10}} if mode == "q8_ring_overlap" else {{}}
    ch = make_channel(mode, mesh, **kw)
    fn = jax.jit(ch.reduce_mean)
    lowered = fn.lower(key, tree)
    coll = collective_bytes(lowered.compile().as_text())
    wire = sum(v for k, v in coll.items() if k != "_counts")
    out = fn(key, tree)
    jax.block_until_ready(out)
    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(jax.random.fold_in(key, 1000 + i), tree))
        times.append(time.perf_counter() - t0)
    times.sort()
    nb = len(plan_buckets(tree, ch.bucket_bytes)) if hasattr(
        ch, "bucket_bytes") else 1
    results[mode] = {{
        "step_time_s": times[len(times) // 2],
        "collective_bytes": int(wire),
        "bucket_count": nb,
        "dense_bytes": int(4 * n_elem),
    }}
print("BENCH_JSON " + json.dumps(results))
"""


def main(steps: int = STEPS, smoke: bool = False):
    steps = max(2, steps)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD.format(steps=steps, smoke=smoke)],
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
            f"overlap bench child failed:\n{r.stdout}\n{r.stderr[-3000:]}"
        )
    results = json.loads(line[len("BENCH_JSON "):])
    write_bench_json(OUT_JSON, results)
    rows = [
        (
            mode,
            f"{m['step_time_s'] * 1e3:.1f}ms",
            f"{m['collective_bytes'] / 1e6:.3f}MB",
            f"{m['collective_bytes'] / m['dense_bytes']:.3f}",
            m["bucket_count"],
        )
        for mode, m in results.items()
    ]
    print_table(
        "Overlap runtime: reduce step over 8 fake devices "
        "(interpret-mode kernels on CPU; bytes are the HLO truth)",
        ["mode", "step", "collective bytes", "vs dense msg", "buckets"],
        rows,
    )
    return results


if __name__ == "__main__":
    main()
