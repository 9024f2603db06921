"""The benchmark harness: one run of one cell on the chips it asks for.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout.  It trains the cell's model through
the system's own entry points (``repro.launch.train``: ``init_state``
laid out by ``state_pspecs``, ``jit_train_step``; ``TokenStream`` and
``device_put`` for every batch), in a closed loop with one step in
flight, and prints one JSON line as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), then ``checks``, each number compared for
``correct`` beside its limit.  The same numbers end standard error.  A
run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.

One run:

1. set-up (``setup_s``, from process start to the first timed step):
   weights made on the chips from ``--seed`` in one jitted call (by the
   reference's own initialiser, so the reference never takes weights
   the system made); the cell's step compiled ahead of time (JAX's
   persistent cache lives in ``.jax_cache/`` of the checkout); the first
   ``COMPARED_STEPS`` steps through the compiled step, reading the
   gradient's per-leaf norms from the optimizer's first moment after
   step 1 and the parameters' change after the last; a few more steps;
2. the window: ``--seconds`` of steps.  Each step's time is the interval
   between consecutive completions (the loss of step i is read after
   step i+1 was dispatched), so the queue never drains;
3. with ``--trace 1``, instead of the timed window, ``TRACE_STEPS``
   steps under the profiler, reduced to the per-layer metrics;
4. the check: the chips' state is freed and the plain reference
   (``bench/reference/``) runs the compared steps from the same weights,
   tokens and keys; ``correct`` holds when every gap is within the
   cell's limits (``bench/limits/<workload>.json``).

Everything is found by name, so a later change adds files and edits
none:

* a cell is an entry of ``workloads`` in ``BENCHMARK.json`` naming a
  ``config`` (``bench/configs/<config>.json``: the system's arch id, the
  model as run, the keys cut from the source) and a ``traffic`` mix
  (``bench/traffic/<traffic>.json``: chips and workers, batch x seq,
  codec, shift rule and comm mode, schedule, optimizer); its limits are
  ``bench/limits/<workload>.json``;
* a per-layer metric is ``bench/metrics/<metric>.py`` with ``LAYER``,
  ``UNIT``, ``MOVES`` and ``read(ctx)``, returning a number or ``None``
  when the trace holds nothing for it (the metric is then left out);
* an architecture is ``bench/reference/archs/<arch_type>.py``, named by
  the ``arch_type`` of the config's ``model``: its plain reference
  (``init_params(key, m)``, ``logits(params, tokens, m, ein)``, built
  from the layers of ``bench/reference/model.py``), its forward FLOPs per
  token (``forward_flops_per_token(m, seq)``, from the pieces of
  ``bench/counts.py``; ``mfu`` reads three times it), and a smoke case
  (``SMOKE``, ``SMOKE_SEQ``, ``SYSTEM_ARCH``) that
  ``bench/test_bench_reference.py`` compares with the system on the CPU.

Worked example: a four-chip cell of qwen3-0.6b whose DIANA messages are
summed by XLA's all-reduce instead of the q8 ring needs
``bench/traffic/dp4.dense.s1024.json`` (a copy of
``dp4.q8ring-overlap.s1024.json`` with ``"comm_mode": "dense"``),
``bench/limits/qwen3-0.6b.dp4.dense.json`` (set from that cell's own
readings, ``bench/calibrate.py``), and a ``workloads`` entry
``{"name": "qwen3-0.6b.dp4.dense", "config": "qwen3-0.6b", "traffic":
"dp4.dense.s1024", "chips": 4, "why": ...}``.  A metric that should
also read there either has no ``workloads`` key or gains the cell's
name in it.

Worked example of a new architecture: a one-chip cell of the system's
``qwen2-moe-a2.7b`` (``arch_type`` ``moe``) adds
``bench/reference/archs/moe.py`` (``init_params``, ``logits`` and
``forward_flops_per_token``, composed from the shared layers and any
layer of its own written in the file; ``SMOKE``, ``SMOKE_SEQ`` and
``SYSTEM_ARCH = "qwen2-moe-a2.7b"``), ``bench/configs/<config>.json``
(``"arch": "qwen2-moe-a2.7b"``, ``"model": {"arch_type": "moe", ...}``),
``bench/traffic/<traffic>.json``, ``bench/limits/<workload>.json`` and
its ``configs`` and ``workloads`` entries in ``BENCHMARK.json``.  It
edits no file: the reference, the FLOP count behind ``mfu``, the check
and the smoke test all find the module by its name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS = BENCH / "runs"
#: steps that the reference follows and the check compares
COMPARED_STEPS = 3
#: steps after the compared ones that settle the loop before the window
WARMUP_STEPS = 2
#: steps under the profiler in a ``--trace 1`` run
TRACE_STEPS = 6


class BenchError(Exception):
    """A run that cannot be made or measured: exit non-zero, no result."""


def load_json(path: pathlib.Path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing {path.relative_to(ROOT)}") from None


@dataclass
class Cell:
    """One workload with everything the harness reads for it."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)

    @property
    def model(self) -> dict:
        """The model description the reference and the counts read: the
        model as run plus the sizes the system fixes in code."""
        return {**self.config["model"], **self.config.get("assumed", {})}


def _reports(metric: dict, cell: str, moved: set) -> bool:
    """A per-layer metric reads in a cell its ``workloads`` list, or, with
    no list, in every cell that reports the metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in moved


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench / "limits" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _reports(m, name, moved)]
    if traffic["chips"] != w["chips"]:
        raise BenchError(f"{name}: traffic {w['traffic']} is for "
                         f"{traffic['chips']} chips, the cell asks for "
                         f"{w['chips']}")
    return Cell(name, w["chips"], config, traffic, limits, e2e, per_layer)


def load_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json "
                         f"(have {sorted(table)})")
    return table[kind]


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader bench/metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """The run's root key from a seed of any size."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


# --------------------------------------------------------------------------
# The system under test, built as its trainer builds it
# --------------------------------------------------------------------------


@dataclass
class System:
    batch_sh: object
    init: object          # seed key -> placed TrainState
    step: object          # the jitted step (jit_train_step)
    stream: object
    grad_norms: object    # opt.m -> per-leaf norms of the first gradient
    change_norms: object  # (params, weights key) -> per-leaf change norms
    names: list


def build_system(cell: Cell, devices, seed: int) -> System:
    import jax
    import jax.numpy as jnp

    from reference import model as RM
    from repro.configs import get_config
    from repro.configs.base import CompressionConfig, TrainConfig
    from repro.data.tokens import TokenStream
    from repro.launch.mesh import make_host_mesh, n_workers
    from repro.launch.train import (
        batch_pspecs,
        init_state,
        jit_train_step,
        named_shardings,
        state_pspecs,
    )
    from repro.models import model as M
    from reference.train import leaf_names
    import tokens as TK

    t, m = cell.traffic, cell.model
    cfg = get_config(cell.config["arch"]).with_(**cell.config["model"])
    comp = CompressionConfig(compressor=t["compressor"],
                             shift_rule=t["shift_rule"],
                             comm_mode=t["comm_mode"],
                             shift_alpha=t["shift_alpha"],
                             q8_block_rows=t["q8_block_rows"])
    s, o = t["schedule"], t["optimizer"]
    tcfg = TrainConfig(learning_rate=s["lr"], warmup_steps=s["warmup_steps"],
                       total_steps=s["total_steps"], beta1=o["beta1"],
                       beta2=o["beta2"], eps=o["eps"],
                       weight_decay=o["weight_decay"], compression=comp)
    mesh = make_host_mesh(devices)
    w = n_workers(mesh)
    if w != t["workers"]:
        raise BenchError(f"mesh gives {w} workers, traffic states "
                         f"{t['workers']}")
    key = seed_key(seed)
    prog = jax.eval_shape(lambda k: M.init_params(k, cfg), key)
    ref = jax.eval_shape(lambda k: RM.init_params(k, m), key)
    if (jax.tree_util.tree_structure(prog) != jax.tree_util.tree_structure(ref)
            or jax.tree_util.tree_leaves(prog) != jax.tree_util.tree_leaves(ref)):
        raise BenchError("the system's parameter tree differs from the "
                         "reference's for this configuration")

    def init(k):
        st = init_state(k, cfg, tcfg, w)
        return st._replace(params=RM.init_params(jax.random.fold_in(k, 0), m),
                           key=jax.random.fold_in(k, 1))

    state_sh = named_shardings(
        state_pspecs(jax.eval_shape(init, key), mesh, tcfg), mesh)
    stream = TokenStream(cfg, t["seq"], t["batch"], seed=TK.data_seed(seed))
    batch_sh = named_shardings(batch_pspecs(stream.batch(0), mesh), mesh)
    b1 = o["beta1"]

    def grad_norms(mom):
        return [jnp.linalg.norm(x) / (1 - b1)
                for x in jax.tree_util.tree_leaves(mom)]

    def change_norms(params, k):
        p0 = RM.init_params(jax.random.fold_in(k, 0), m)
        return [jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
                for a, b in zip(jax.tree_util.tree_leaves(params),
                                jax.tree_util.tree_leaves(p0))]

    return System(batch_sh,
                  jax.jit(init, out_shardings=state_sh),
                  jit_train_step(cfg, tcfg, mesh, w, state_sh), stream,
                  jax.jit(grad_norms), jax.jit(change_norms),
                  leaf_names(ref))


def place_batch(sys_, i: int):
    import jax

    with jax.profiler.TraceAnnotation("bench/input"):
        return jax.device_put(sys_.stream.batch(i), sys_.batch_sh)


def compared_steps(sys_: System, compiled, state, n: int, key):
    """The first ``n`` steps through the compiled step, reading what the
    reference is compared on.  Returns the state, the steps' losses
    (device scalars) and the readings (device arrays)."""
    losses, obs = [], {}
    for i in range(n):
        state, met = compiled(state, place_batch(sys_, i))
        losses.append(met["loss"])
        if i == 0:
            obs["grad_norm"] = sys_.grad_norms(state.opt.m)
    obs["change_norm"] = sys_.change_norms(state.params, key)
    return state, losses, obs


def readings(sys_: System, losses, obs) -> dict:
    """The compared steps' readings as host numbers, in the form
    ``reference.train.gaps`` takes."""
    return {"loss": [float(x) for x in losses],
            "grad_norm": dict(zip(sys_.names, map(float, obs["grad_norm"]))),
            "change_norm": dict(zip(sys_.names,
                                    map(float, obs["change_norm"])))}


def closed_loop(sys_, compiled, state, first: int, n_steps=None,
                seconds=None):
    """Steps from index ``first`` with one step in flight, until
    ``n_steps`` are done or ``seconds`` have passed.  Returns the state,
    the losses and the completion times (the first entry is the start)."""
    import jax

    t0 = time.perf_counter()
    done, losses, pending, i = [t0], [], None, first
    while True:
        b = place_batch(sys_, i)
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            state, met = compiled(state, b)
        i += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("bench/readback"):
                losses.append(float(pending))
            done.append(time.perf_counter())
        pending = met["loss"]
        if n_steps is not None and i - first >= n_steps:
            break
        if seconds is not None and done[-1] - t0 >= seconds:
            break
    with jax.profiler.TraceAnnotation("bench/readback"):
        losses.append(float(pending))
    done.append(time.perf_counter())
    return state, losses, done


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------


@dataclass
class Ctx:
    """What a per-layer reader gets."""

    trace: dict
    window: tuple
    steps: int
    tokens_per_step: int
    chips: int
    peaks: dict
    flops_per_token: float
    q8_bytes_per_step: float


def _count_compiles():
    """Counts, while ``box["on"]``, the programs JAX asked the backend for
    and how many of them came from the persistent cache."""
    import jax

    box = {"n": 0, "hits": 0, "on": False}

    def timed(event, *_a, **_k):
        if box["on"] and event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    def counted(event, **_k):
        if box["on"] and event == "/jax/compilation_cache/cache_hits":
            box["hits"] += 1
    jax.monitoring.register_event_duration_secs_listener(timed)
    jax.monitoring.register_event_listener(counted)
    return box


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, t_start: float = None):
    """One run of ``cell``; returns the result dict and the check lines."""
    import jax
    import numpy as np

    import counts
    import tokens as TK
    import trace_reduce as TR
    from reference.train import Reference, gaps

    t_start = T_START if t_start is None else t_start
    t = cell.traffic
    compiles = _count_compiles()
    sys_ = build_system(cell, devices, seed)
    key = seed_key(seed)
    state = sys_.init(key)
    b0 = place_batch(sys_, 0)
    compiled = sys_.step.lower(state, b0).compile()
    hlo = compiled.as_text()
    scopes = {TR.module_name(hlo): TR.op_scopes(hlo)}
    del hlo
    ma = compiled.memory_analysis()
    step_hbm = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    del b0
    n_cmp = COMPARED_STEPS
    state, cmp_losses, obs = compared_steps(sys_, compiled, state, n_cmp, key)
    state, warm_losses, _ = closed_loop(sys_, compiled, state, n_cmp,
                                        n_steps=WARMUP_STEPS)
    first = n_cmp + WARMUP_STEPS
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start
    compiles["on"] = True
    if trace:
        tdir = RUNS / f"trace-{cell.name}"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        state, win_losses, done = closed_loop(sys_, compiled, state, first,
                                              n_steps=TRACE_STEPS)
        jax.profiler.stop_trace()
    else:
        state, win_losses, done = closed_loop(sys_, compiled, state, first,
                                              seconds=seconds)
    compiles["on"] = False
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    prog = readings(sys_, cmp_losses, obs)
    losses = prog["loss"] + warm_losses + win_losses
    del state, compiled, obs, cmp_losses
    gc.collect()

    n_win = len(win_losses)
    tokens_per_step = t["batch"] * t["seq"]
    metrics, breakdown, dev_extra = {}, None, {}
    if trace:
        red = TR.load(str(tdir), scopes)
        TR.save(red, str(tdir / "reduced.json"))
        lo, hi = TR.window(red)
        n_dev = len(red["devices"])
        if n_dev == 0:
            raise BenchError("the trace holds no device operations")
        busy = TR.mean_over_devices(red, lambda ops: TR.busy_ns(ops, lo, hi))
        dev_extra = {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9}
        leaves = [(int(math.prod(a.shape)), a.dtype.itemsize)
                  for a in jax.tree_util.tree_leaves(
                      jax.eval_shape(lambda k: _ref_init(cell, k), key))]
        ctx = Ctx(red, (lo, hi), n_win, tokens_per_step, cell.chips, peaks,
                  counts.model_flops_per_token(cell.model, t["seq"]),
                  counts.q8_bytes_per_step(leaves,
                                           t["workers"] // cell.chips,
                                           cell.chips, t["q8_block_rows"]))
        for m in cell.per_layer:
            v = load_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": TR.top_ops(red, lo, hi),
                     "idle_gaps": TR.idle_gaps(red, lo, hi)}
    else:
        step_ms = [(b - a) * 1e3 for a, b in zip(done, done[1:])]
        values = {
            "tokens_per_s": tokens_per_step * n_win / (done[-1] - done[0]),
            "step_ms_p90": float(np.percentile(step_ms, 90)),
            "step_hbm_gb": step_hbm / 1e9,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # the check: the plain reference over the compared steps
    batches = [np.asarray(TK.batch(seed, i, t["batch"], t["seq"],
                                   cell.model["vocab_size"]))
               for i in range(n_cmp)]
    fed = [np.asarray(sys_.stream.batch(i)["tokens"]) for i in range(n_cmp)]
    token_diff = sum(int(np.sum(a != b)) for a, b in zip(batches, fed))
    t_ref = time.perf_counter()
    params0 = jax.jit(lambda k: _ref_init(cell, k))(jax.random.fold_in(key, 0))
    ref = Reference(cell.model, t, devices=devices).run(
        params0, batches, jax.random.fold_in(key, 1), n_cmp)
    del params0
    ref_s = time.perf_counter() - t_ref
    found = gaps(prog, ref)
    found["token_diff"] = token_diff
    finite = all(math.isfinite(x) for x in losses)
    checks = {n: {"value": v, "limit": cell.limits[n]}
              for n, v in found.items()}
    correct = finite and all(c["value"] <= c["limit"]
                             for c in checks.values())
    failed = sum(1 for x in win_losses if not math.isfinite(x))
    dev = devices[0]
    result = {
        "correct": correct,
        "attempted": n_win,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak_mem,
                   **dev_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    notes = [f"setup_s {setup_s!r}  step_hbm_bytes {step_hbm}  "
             f"peak_bytes_in_use {peak_mem}  window compile requests "
             f"{compiles['n']} (persistent-cache hits {compiles['hits']})  "
             f"steps {len(losses)}  reference_s {ref_s!r}",
             f"losses program {prog['loss']!r}  reference {ref['loss']!r}"]
    return result, notes


def _ref_init(cell: Cell, k):
    from reference import model as RM

    return RM.init_params(k, cell.model)


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_paths(root: pathlib.Path = ROOT) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no system under test: {src}/repro is missing")
    for p in (str(src), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
        setup_paths()
        import jax

        from repro.launch.cache import use_compile_cache

        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < cell.chips:
            raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX "
                             f"found {len(devices)}")
        peaks = load_peaks(devices[0].device_kind)
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        result, notes = run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), devices[:cell.chips],
                                 peaks)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in notes:
        print(line, file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
