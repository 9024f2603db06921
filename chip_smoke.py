"""On-chip smoke run of the data-parallel shifted-compression trainer.

Drives the trainer's own functions (``init_placed_state``,
``jit_train_step``, ``TokenStream``) once on a TPU at the full published
width of qwen3-0.6b (28 layers, d_model 1024, vocab 151936, bf16), with
random weights from a fixed seed, and checks what comes out.

  python chip_smoke.py             one chip: DIANA with the q8_block
                                   codec on the dense comm mode, so every
                                   param leaf goes through the compiled
                                   q8 Pallas kernels; plus the kernels on
                                   an MLP leaf and on the embedding (also
                                   as a four-way ring chunk) against
                                   ``kernels/q8ring/ref.py``
  python chip_smoke.py --chips 4   four chips, W = 4 data-parallel
                                   workers: DIANA over the dense, the
                                   q8_ring_overlap and the
                                   q8_ring_fused_vjp exchanges, compared

Step times printed here are smoke timings of a few steps, not a
benchmark.  One process drives every chip it uses.  The last line of
standard output is ``{"ok": true, "device": {...}}``; any failed check
exits non-zero before it is printed, and so does a run that finds no
TPU.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen3-0.6b"
SEED = 0
STEPS = 3
LR = 3e-4
#: one-chip batch x seq: the largest tried whose compiled step leaves at
#: least HBM_FREE of the chip's memory free (memory_analysis, v5e)
ONE_CHIP_BATCH, ONE_CHIP_SEQ = 8, 256
#: four-chip global batch (W = 4 workers x 8 rows) x seq
FOUR_CHIP_BATCH, FOUR_CHIP_SEQ = 32, 256
HBM_FREE = 0.15
FOUR_CHIP_MODES = ("dense", "q8_ring_overlap", "q8_ring_fused_vjp")
#: step-0 losses of the modes (same params, same batch): equal up to
#: the float32 rounding of differently fused programs
STEP0_LOSS_RTOL = 1e-6
#: fused_vjp vs overlap: ||p_fused - p_overlap|| over the distance the
#: overlap run's params moved (0 when bitwise equal)
FUSED_VS_OVERLAP_RTOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def train_configs(mode: str):
    """(ModelConfig, TrainConfig) of one smoke run, as ``train.main``
    builds them for ``--compressor q8_block --shift-rule diana``."""
    from repro.configs import get_config
    from repro.configs.base import CompressionConfig, TrainConfig

    cfg = get_config(ARCH)
    comp = CompressionConfig(compressor="q8_block", shift_rule="diana",
                             comm_mode=mode)
    tcfg = TrainConfig(learning_rate=LR, total_steps=STEPS,
                       warmup_steps=max(1, STEPS // 10), compression=comp)
    return cfg, tcfg


def build_run(mode: str, devices, batch: int, seq: int):
    """Placed initial state, jitted step and batch shardings of one run
    on a ("data", "model") = (len(devices), 1) mesh."""
    import jax

    from repro.data.tokens import TokenStream
    from repro.launch.mesh import make_host_mesh, n_workers
    from repro.launch.train import (
        batch_pspecs,
        init_placed_state,
        jit_train_step,
        named_shardings,
    )

    cfg, tcfg = train_configs(mode)
    mesh = make_host_mesh(devices)
    w = n_workers(mesh)
    state, state_sh = init_placed_state(jax.random.PRNGKey(SEED), cfg, tcfg,
                                        mesh, w)
    step = jit_train_step(cfg, tcfg, mesh, w, state_sh)
    stream = TokenStream(cfg, seq, batch, seed=SEED)
    batch_sh = named_shardings(batch_pspecs(stream.batch(0), mesh), mesh)
    batches = [jax.device_put(stream.batch(i), batch_sh)
               for i in range(STEPS + 1)]
    return state, step, batches


def compile_step(step, state, batch, hbm_limit: int):
    """AOT-compile the step; check that it fits with HBM_FREE to spare
    and that the q8 kernels were compiled, not interpreted."""
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    hlo = compiled.as_text()
    n_kernels = hlo.count("tpu_custom_call")
    print(f"compile_s {compile_s:.3f}  program_bytes {need} "
          f"(args {ma.argument_size_in_bytes} temps {ma.temp_size_in_bytes} "
          f"aliased {ma.alias_size_in_bytes})  hbm_limit {hbm_limit}  "
          f"tpu_custom_call {n_kernels}")
    check(need <= (1.0 - HBM_FREE) * hbm_limit,
          f"step needs {need} B, more than {1 - HBM_FREE:.0%} of {hbm_limit}")
    check(n_kernels > 0, "no tpu_custom_call in the compiled step: the q8 "
          "kernels ran in interpret mode")
    return compiled, hlo


def run_steps(compiled, state, batches):
    """Warm-up step, then STEPS timed steps.  Returns the final state,
    the per-step metrics (host floats) and the timed steps' seconds."""
    import jax
    import numpy as np

    metrics, times = [], []
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        state, m = compiled(state, b)
        jax.block_until_ready((state, m))
        if i:
            times.append(time.perf_counter() - t0)
        m = {k: float(v) for k, v in m.items()}
        check(np.isfinite(m["loss"]), f"step {i}: loss {m['loss']}")
        check(m["bits"] > 0, f"step {i}: bits {m['bits']}")
        metrics.append(m)
    return state, metrics, times


def _check_against_ref(what, q, s, out, ref, acc=None):
    """One kernel pass against its oracles: the int8 payload and the
    scales, and ``out`` = acc + dequant(payload) (acc None: zero).  The
    sum may round once less or once more than the oracle's, so its
    bound is relative to |acc| + |dequant|, not to the sum."""
    import numpy as np

    qr, sr, out_ref = ref
    q, qr = np.asarray(q, np.int32), np.asarray(qr, np.int32)
    s, sr = np.asarray(s), np.asarray(sr)
    out, out_ref = np.asarray(out), np.asarray(out_ref)
    acc = np.zeros_like(out_ref) if acc is None else np.asarray(acc)
    qdiff = int(np.abs(q - qr).max())
    srel = float(np.max(np.abs(s - sr) / sr))
    ddiff = np.abs(out - out_ref)
    print(f"{what}: int8 max|q - ref| {qdiff} (share equal "
          f"{float(np.mean(q == qr))!r}), scale max rel {srel!r} (share "
          f"equal {float(np.mean(s == sr))!r}), dequant max|d - ref| "
          f"{float(ddiff.max())!r} (share equal "
          f"{float(np.mean(ddiff == 0))!r})")
    check(qdiff <= 1, f"{what}: int8 payload off the reference by {qdiff}")
    check(srel <= 1e-6, f"{what}: scales off the reference by rel {srel}")
    check((ddiff <= 1e-6 * (np.abs(acc) + np.abs(out_ref - acc))).all(),
          f"{what}: dequant off the reference")


def check_leaf_kernels(leaf, ring_chunks: int = 0):
    """The chip's q8_quantize_2d + q8_dequant against the jnp oracles of
    ``kernels/q8ring/ref.py`` on one real leaf, with the same uniforms;
    with ``ring_chunks`` = n also the ring hop's kernels on the leaf cut
    as an n-way ring cuts it: q8_quantize_chunk_3d on the last chunk and
    q8_dequant_add_2d of its payload onto the first."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.q8ring.kernel import (
        LANE,
        q8_dequant_add_2d,
        q8_quantize_2d,
        q8_quantize_chunk_3d,
    )
    from repro.kernels.q8ring.ops import (
        q8_dequant,
        q8_layout,
        ring_chunk_layout,
        to_lanes,
    )
    from repro.kernels.q8ring.ref import q8_dequant_add_ref, q8_quantize_ref

    d = int(leaf.size)
    _, block, rows_pad = q8_layout(d)
    x = to_lanes(leaf, rows_pad)
    u = jax.random.uniform(jax.random.PRNGKey(SEED + 1), x.shape)
    q, s = q8_quantize_2d(x, u, block_rows=block, interpret=False)
    deq = q8_dequant(q, s, block=block, interpret=False)
    zeros = jnp.zeros_like(x)
    _check_against_ref(
        f"leaf {tuple(leaf.shape)}", q, s, deq,
        (*q8_quantize_ref(x, u, block=block),
         q8_dequant_add_ref(q, s, zeros, block=block)))
    del x, u, q, s, deq, zeros
    if not ring_chunks:
        return
    n = ring_chunks
    rows_c, block = ring_chunk_layout(d, n)
    chunks = to_lanes(leaf, n * rows_c).reshape(n, rows_c, LANE)
    u = jax.random.uniform(jax.random.PRNGKey(SEED + 2), (rows_c, LANE))
    q, s = q8_quantize_chunk_3d(chunks, u, n - 1, block_rows=block,
                                interpret=False)
    out = q8_dequant_add_2d(q, s, chunks[0], block_rows=block,
                            interpret=False)
    _check_against_ref(
        f"leaf {tuple(leaf.shape)} ring chunk {n - 1} of {n} "
        f"({rows_c // block} tiles)", q, s, out,
        (*q8_quantize_ref(chunks[n - 1], u, block=block),
         q8_dequant_add_ref(q, s, chunks[0], block=block)), acc=chunks[0])


def one_chip(devices):
    dev = devices[0]
    state, step, batches = build_run("dense", devices, ONE_CHIP_BATCH,
                                     ONE_CHIP_SEQ)
    compiled, _ = compile_step(step, state, batches[0],
                               dev.memory_stats()["bytes_limit"])
    state, metrics, times = run_steps(compiled, state, batches)
    leaves = (state.params["blocks"]["mlp"]["w_up"][0].astype("float32"),
              state.params["embed"]["table"].astype("float32"))
    for i, m in enumerate(metrics):
        print(f"step {i} loss {m['loss']!r} bits {m['bits']!r}")
    print(f"smoke step_s (not a benchmark) {times!r}  "
          f"batch {ONE_CHIP_BATCH} x seq {ONE_CHIP_SEQ}")
    print(f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")
    del state
    check_leaf_kernels(leaves[0])
    check_leaf_kernels(leaves[1], ring_chunks=4)


def _host(tree):
    """A device pytree's leaves as host arrays in their own dtype."""
    import jax

    return jax.tree_util.tree_leaves(jax.device_get(tree))


def _l2(xs, ys):
    """||xs - ys|| over two lists of leaves, leaf by leaf in float32."""
    import numpy as np

    return sum(float(np.sum((np.asarray(x, np.float32)
                             - np.asarray(y, np.float32)) ** 2))
               for x, y in zip(xs, ys)) ** 0.5


def placed_state_report(state, devices):
    """Bytes of the optimizer moments on each device, and each device's
    bytes_in_use, right after the state was placed."""
    import jax

    opt_local = [0] * len(devices)
    for leaf in jax.tree_util.tree_leaves(state.opt):
        for sh in leaf.addressable_shards:
            opt_local[devices.index(sh.device)] += sh.data.nbytes
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    print(f"placed state: optimizer bytes per device {opt_local}  "
          f"bytes_in_use per device {in_use}")
    check(max(opt_local) <= 1.01 * min(opt_local),
          "optimizer state is not spread over the chips")
    check(max(in_use) <= 1.25 * min(in_use),
          "device 0 holds more of the state than the others")


def four_chips(devices):
    import numpy as np

    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    devices = devices[:4]
    loss0, kept = {}, {}
    for mode in FOUR_CHIP_MODES:
        state, step, batches = build_run(mode, devices, FOUR_CHIP_BATCH,
                                         FOUR_CHIP_SEQ)
        if mode == FOUR_CHIP_MODES[0]:
            placed_state_report(state, devices)
        if mode == "q8_ring_overlap":
            kept["p0"] = _host(state.params)
        compiled, hlo = compile_step(step, state, batches[0],
                                     devices[0].memory_stats()["bytes_limit"])
        if mode != "dense":
            check("collective-permute" in hlo,
                  f"{mode}: no collective-permute in the compiled step")
        state, metrics, times = run_steps(compiled, state, batches)
        loss0[mode] = metrics[0]["loss"]
        print(f"{mode}: losses {[m['loss'] for m in metrics]!r}  "
              f"bits {metrics[-1]['bits']!r}  smoke step_s (not a "
              f"benchmark) {times!r}")
        print(f"{mode}: peak_bytes_in_use per device "
              f"{[d.memory_stats()['peak_bytes_in_use'] for d in devices]}")
        if mode != "dense":
            kept[mode] = _host(state.params)
        del state, compiled

    spread = max(loss0.values()) - min(loss0.values())
    print(f"step-0 loss per mode {loss0!r}  bitwise equal "
          f"{len(set(loss0.values())) == 1}")
    check(spread <= STEP0_LOSS_RTOL * abs(loss0["dense"]),
          f"step-0 losses differ across modes by {spread}")

    fused, overlap = kept["q8_ring_fused_vjp"], kept["q8_ring_overlap"]
    moved = _l2(overlap, kept["p0"])
    diff = _l2(fused, overlap)
    bitwise = all(np.array_equal(x, y) for x, y in zip(fused, overlap))
    print(f"fused_vjp vs overlap after {STEPS + 1} steps: params bitwise "
          f"equal {bitwise}  ||p_fused - p_overlap|| {diff!r}  "
          f"||p_overlap - p0|| {moved!r}  ratio {diff / moved!r} "
          f"(tolerance {FUSED_VS_OVERLAP_RTOL})")
    check(moved > 0, "the overlap run's params did not move")
    check(diff <= FUSED_VS_OVERLAP_RTOL * moved,
          "fused_vjp params are off the overlap run's beyond tolerance")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip training smoke; 4: "
                         "the four-chip data-parallel comparison only")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    import jax

    from repro.launch.cache import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache()}")
    try:
        if args.chips == 4:
            four_chips(devices)
        else:
            one_chip(devices[:1])
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
