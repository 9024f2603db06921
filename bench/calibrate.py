"""Readings that a cell's limits are set from (``bench/limits/``).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,...,12 \
        [--control-seeds 1,2,3] [--faults half_batch,loss_altered] \
        [--out readings.jsonl]

In one process (the step is compiled once), for each seed:

* ``program``: the system's compared steps at the cell's own size, as a
  run of ``bench/run.py`` takes them, against the reference;
* ``control``: the reference computed with float8 matrix products, put
  in the system's place (on the control seeds);
* ``fault:<name>``: the reference with one planted fault
  (``reference.train.Reference``) in the system's place.

Each line of the output is ``{"seed", "kind", <number>: gap, ...}``.  A
limit lies above every ``program`` reading and below the smallest
reading of the control and of each fault that reads at least ten times
the largest ``program`` one (a state left unchanged: three times).
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys

import run as R


def _ints(text: str):
    return [int(x) for x in text.split(",") if x]


def calibrate(cell, devices, seeds, control_seeds, faults, emit) -> None:
    """Emit the readings of ``cell`` for every seed (see module doc)."""
    import jax
    import numpy as np

    import tokens as TK
    from reference.train import Reference, gaps

    t = cell.traffic
    n = R.COMPARED_STEPS
    sys_ = R.build_system(cell, devices, seeds[0])
    compiled = None
    for seed in sorted(set(seeds) | set(control_seeds)):
        key = R.seed_key(seed)
        batches = [np.asarray(TK.batch(seed, i, t["batch"], t["seq"],
                                       cell.model["vocab_size"]))
                   for i in range(n)]
        params0 = jax.jit(lambda k: R._ref_init(cell, k))(
            jax.random.fold_in(key, 0))
        skey = jax.random.fold_in(key, 1)
        ref = None
        if seed in seeds:
            sys_.stream = dataclasses.replace(sys_.stream,
                                              seed=TK.data_seed(seed))
            state = sys_.init(key)
            if compiled is None:
                compiled = sys_.step.lower(
                    state, R.place_batch(sys_, 0)).compile()
            state, losses, obs = R.compared_steps(sys_, compiled, state, n,
                                                  key)
            prog = R.readings(sys_, losses, obs)
            del state, obs
            gc.collect()
            ref = Reference(cell.model, t, devices=devices).run(
                params0, batches, skey, n)
            emit({"seed": seed, "kind": "program",
                  **gaps(prog, ref)})
        if seed in control_seeds:
            if ref is None:
                ref = Reference(cell.model, t, devices=devices).run(
                    params0, batches, skey, n)
            others = [("control", dict(precision="fp8"))] + [
                (f"fault:{f}", dict(fault=f)) for f in faults]
            for kind, kw in others:
                got = Reference(cell.model, t, devices=devices, **kw).run(
                    params0, batches, skey, n)
                emit({"seed": seed, "kind": kind,
                      **gaps(got, ref)})
        del params0
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    R.setup_paths()
    import jax

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()[:cell.chips]
    print(f"device {devices[0].device_kind} x {len(devices)}", flush=True)
    out = open(args.out, "w") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        calibrate(cell, devices, args.seeds, args.control_seeds,
                  [f for f in args.faults.split(",") if f], emit)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
