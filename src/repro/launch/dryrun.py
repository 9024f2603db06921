import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod AOT dry-run: lower + compile every (architecture x input
shape x mesh) combination against 512 placeholder devices; record
memory_analysis, cost_analysis and the collective-bytes HLO parse for
the roofline table (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes

Outputs one JSON per combination under experiments/dryrun/.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import CompressionConfig, InputShape, ModelConfig, TrainConfig
from repro.data.tokens import make_batch_specs
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh, n_workers
from repro.launch.serve import decode_specs, decode_state_pspecs, serving_config
from repro.launch.train import (
    COMM_MODES,
    batch_pspecs,
    build_train_step,
    init_state,
    named_shardings,
    state_pspecs,
)
from repro.models import model as M

tmap = jax.tree_util.tree_map


def skip_reason(arch: str, shape: InputShape) -> str | None:
    cfg = get_config(arch)
    if shape.name == "long_500k" and cfg.arch_type == "audio":
        return "long_500k skipped for audio enc-dec (DESIGN.md §Arch-applicability)"
    return None


def tune_preview(cfg: ModelConfig, comp: CompressionConfig, mesh,
                 analysis: Dict[str, Any], top: int = 5,
                 wire_traffic=None) -> Dict[str, Any]:
    """Predicted-vs-chosen comm plans for this (arch x mesh) workload.

    AOT-only: the tuner's predictor runs off this dry-run's loop-aware
    HLO analysis, nominal TPU link/device rates, and structural wire
    bits (``verify_top=0`` — nothing is timed on the dry-run host).
    The full measured search belongs to ``--comm_mode auto`` at launch;
    this preview shows what it WOULD choose next to what is configured.
    With registered non-grad wires (``wire_traffic``) the grid also
    crosses each configured wire flag against ``"none"`` so the preview
    shows whether compressing that wire pays off.
    """
    from repro import tune
    from repro.launch.mesh import n_workers

    w = n_workers(mesh)
    params_shapes = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    wlike = tmap(
        lambda p: jax.ShapeDtypeStruct((w, *p.shape), p.dtype), params_shapes
    )
    grids = {}
    if comp.moe_wire != "none":
        grids["moe_wire_grid"] = tuple(dict.fromkeys(("none", comp.moe_wire)))
    if comp.act_wire != "none":
        grids["act_wire_grid"] = tuple(dict.fromkeys(("none", comp.act_wire)))
    if comp.model_wire != "none":
        grids["model_wire_grid"] = tuple(
            dict.fromkeys(("none", comp.model_wire))
        )
    plan = tune.search_plan(
        comp, wlike, mesh, w, fingerprint="preview", analysis=analysis,
        link=tune.LinkModel.nominal(), rates=tune.DeviceRates.nominal(),
        verify_top=0, wire_traffic=wire_traffic, **grids,
    )
    return {
        "configured_comm_mode": comp.comm_mode,
        "predicted_choice": plan.comm_mode,
        "predicted_moe_wire": plan.moe_wire,
        "predicted_act_wire": plan.act_wire,
        "predicted_model_wire": plan.model_wire,
        "predicted_step_s": plan.predicted_step_s,
        # which overlap-hide fed the composition: "nominal" here (AOT
        # preview — nothing is measured); a launch-time search records
        # the measured fraction in its TunePlan and the obs run header
        "hide_fraction": plan.hide_fraction,
        "hide_source": plan.hide_source,
        # likewise the compressor variance: "analytic" here (the AOT
        # preview never runs traffic); a launch-time measured probe
        # records omega_source="measured" instead
        "omega": plan.omega,
        "omega_source": plan.omega_source,
        "candidates": list(plan.candidates[:top]),
    }


def accounting_transport(cfg: ModelConfig, comp: CompressionConfig, mesh,
                         shape: InputShape):
    """The Transport this run registers, channel-free (accounting only):
    grad traffic from the parameter tree, moe/act traffic from the input
    shape's per-worker token count."""
    from repro.comm import build_transport

    w = n_workers(mesh)
    params_shapes = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    return build_transport(
        comp, cfg, None, w=w, params_like=params_shapes,
        tokens_per_worker=shape.global_batch * shape.seq_len // max(w, 1),
    )


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """6*N*D for training, 2*N*D forward-only; N = active params."""
    n = M.count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n * tokens


def lower_train(cfg: ModelConfig, shape: InputShape, mesh,
                tcfg: TrainConfig):
    w = n_workers(mesh)
    step = build_train_step(cfg, tcfg, mesh, w)
    state_shapes = jax.eval_shape(
        lambda k: init_state(k, cfg, tcfg, w), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    st_specs = state_pspecs(state_shapes, mesh, tcfg)
    batch_shapes = make_batch_specs(cfg, shape)
    b_specs = batch_pspecs(batch_shapes, mesh)
    with jax.sharding.set_mesh(mesh):
        jfn = jax.jit(
            step,
            in_shardings=(named_shardings(st_specs, mesh), named_shardings(b_specs, mesh)),
            out_shardings=(named_shardings(st_specs, mesh), None),
            donate_argnums=(0,),
        )
        return jfn.lower(state_shapes, batch_shapes)


def lower_eval(cfg: ModelConfig, shape: InputShape, mesh):
    """Prefill = forward pass over the full sequence (logits only)."""
    from repro.dist import params_pspecs, validate_pspecs

    def eval_step(params, batch):
        logits, _ = M.forward_train(params, cfg, batch)
        return logits[:, -1]

    params_shapes = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    p_specs = validate_pspecs(
        params_shapes, params_pspecs(params_shapes), mesh
    )
    batch_shapes = make_batch_specs(cfg, shape)
    b_specs = batch_pspecs(batch_shapes, mesh)
    with jax.sharding.set_mesh(mesh):
        jfn = jax.jit(
            eval_step,
            in_shardings=(named_shardings(p_specs, mesh), named_shardings(b_specs, mesh)),
            out_shardings=None,
        )
        return jfn.lower(params_shapes, batch_shapes)


def lower_decode(cfg: ModelConfig, shape: InputShape, mesh):
    from repro.dist import params_pspecs, validate_pspecs
    from repro.launch.serve import build_serve_step

    scfg = serving_config(cfg, shape.name)
    params_shapes, state_shapes, tok, pos = decode_specs(
        scfg, shape.seq_len, shape.global_batch
    )
    p_specs = validate_pspecs(params_shapes, params_pspecs(params_shapes), mesh)
    s_specs = decode_state_pspecs(state_shapes, mesh)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tok_spec = P(data_axes)
    # downgrade tok batch spec if indivisible (long_500k B=1)
    nshards = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in data_axes:
        nshards *= sizes[a]
    if tok.shape[0] % nshards:
        tok_spec = P()
    step = build_serve_step(scfg)
    with jax.sharding.set_mesh(mesh):
        jfn = jax.jit(
            step,
            in_shardings=(
                named_shardings(p_specs, mesh),
                named_shardings(s_specs, mesh),
                NamedSharding(mesh, tok_spec),
                NamedSharding(mesh, P()),
            ),
            out_shardings=(None, named_shardings(s_specs, mesh)),
        )
        return jfn.lower(params_shapes, state_shapes, tok, pos)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            tcfg: TrainConfig, out_dir: str, save_hlo: bool = False,
            probe_quality: bool = False) -> Dict[str, Any]:
    shape = INPUT_SHAPES[shape_name]
    mesh_tag = "pod512" if multi_pod else "pod256"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "kind": shape.kind,
    }
    reason = skip_reason(arch, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    cfg = get_config(arch)
    # per-arch wire sanitization: under --all a moe/act wire flag only
    # applies to the archs that have that wire (a dense model has no
    # expert all-to-all) — drop it rather than failing the combination
    comp = tcfg.compression
    drop = {}
    if comp.moe_wire != "none" and not cfg.is_moe:
        drop["moe_wire"] = "none"
    if comp.act_wire != "none" and cfg.arch_type not in ("dense", "vlm",
                                                         "moe"):
        drop["act_wire"] = "none"
    if drop:
        tcfg = dataclasses.replace(
            tcfg, compression=dataclasses.replace(comp, **drop)
        )
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        if shape.kind == "train":
            lowered = lower_train(cfg, shape, mesh, tcfg)
        elif shape.kind == "prefill":
            lowered = lower_eval(cfg, shape, mesh)
        else:
            lowered = lower_decode(cfg, shape, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # jaxlib < 0.5 returns [dict]
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        from repro.comm import collective_payload_scale
        from repro.launch import hlo_cost
        corrected = hlo_cost.analyze(hlo)
        scale = (
            collective_payload_scale(tcfg.compression)
            if shape.kind == "train" else {}
        )
        if scale:
            # re-charge only the gradient-mean share of the all-reduce
            # bytes at the codec wire fraction; activation collectives
            # stay structural.  The per-DEVICE gradient message is the
            # param tree sharded over the model axis only (the data/pod
            # reduction replicates over those axes), so divide by the
            # model-axis size, not the chip count.
            import numpy as np
            params_shapes = jax.eval_shape(
                lambda k: M.init_params(k, cfg),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            )
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            msg_bytes = sum(
                int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(params_shapes)
            ) / sizes.get("model", 1)
            corrected = hlo_cost.apply_gradient_payload_model(
                corrected, "all-reduce", msg_bytes, scale["all-reduce"]
            )
        coll = hlo_stats.collective_bytes(hlo)  # static instruction counts
        mf = model_flops(
            serving_config(cfg, shape_name) if shape.kind == "decode" else cfg,
            shape,
        )
        n_chips = 512 if multi_pod else 256
        roof = hlo_stats.roofline(corrected, cost, mf, n_chips)

        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            # cost-model blind spots MUST be visible: a while whose trip
            # count fell back to 1 silently under-counts that loop in
            # every roofline/tuner number derived from this analysis
            "cost_model": {
                "unresolved_whiles": list(corrected["unresolved_whiles"]),
                "unresolved_while_count":
                    len(corrected["unresolved_whiles"]),
                "while_trips": dict(corrected["while_trips"]),
            },
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            },
            "roofline": roof,
            "collective_counts": coll.get("_counts"),
        })
        if shape.kind == "train":
            transport = accounting_transport(cfg, tcfg.compression, mesh,
                                             shape)
            rec["wires"] = [
                {
                    "name": wire.name,
                    "topology": wire.topology,
                    "codec": type(wire.codec).__name__,
                    "bytes_per_step": wire.wire_bits() / 8.0,
                    "overlap_hidden": wire.overlap_hidden,
                    # measured distortion is opt-in on the dry-run host:
                    # encoding a synthetic payload per wire is cheap for
                    # the rank/quant codecs but interpret-mode fused
                    # codecs pay real time — dash in the table until run
                    **(wire.codec_quality() if probe_quality
                       else {"omega_hat": None, "nmse": None}),
                }
                for wire in transport
            ]
            if tcfg.compression.enabled:
                rec["tune_preview"] = tune_preview(
                    cfg, tcfg.compression, mesh, corrected,
                    wire_traffic=transport.extra_traffic(),
                )
        if save_hlo:
            with open(os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_tag}.hlo"), "w") as f:
                f.write(hlo)
    except Exception as e:  # record failures — they are bugs to fix
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--comm-mode", "--comm_mode", dest="comm_mode",
                    default="dense", choices=list(COMM_MODES))
    ap.add_argument("--compressor", default="natural")
    ap.add_argument("--shift-rule", "--shift_rule", dest="shift_rule",
                    default="diana")
    from repro.comm import WIRE_CODEC_FLAGS
    ap.add_argument("--moe-wire", "--moe_wire", dest="moe_wire",
                    default="none", choices=list(WIRE_CODEC_FLAGS))
    ap.add_argument("--act-wire", "--act_wire", dest="act_wire",
                    default="none", choices=list(WIRE_CODEC_FLAGS))
    ap.add_argument("--model-wire", "--model_wire", dest="model_wire",
                    default="none", choices=list(WIRE_CODEC_FLAGS),
                    help="trainer->serving model-delta downlink codec")
    ap.add_argument("--publish_every", "--publish-every",
                    dest="publish_every", type=int, default=1,
                    help="steps between downlink publishes (amortizes "
                         "the model wire's bytes/step)")
    ap.add_argument("--no-compression", action="store_true")
    ap.add_argument("--probe-quality", "--probe_quality",
                    dest="probe_quality", action="store_true",
                    help="run the measured omega_hat/NMSE distortion "
                         "probe on each wire's codec (off by default: "
                         "the per-wire table shows a dash)")
    ap.add_argument("--metrics_out", "--metrics-out", dest="metrics_out",
                    default=None,
                    help="emit one obs event per combination (status, "
                         "unresolved-while count) as strict JSONL")
    args = ap.parse_args(argv)

    sink = None
    if args.metrics_out:
        from repro import obs

        sink = obs.JsonlSink(args.metrics_out)
        sink.emit(obs.run_record("dryrun", comm_mode=args.comm_mode))

    os.makedirs(args.out, exist_ok=True)
    tcfg = TrainConfig(
        compression=CompressionConfig(
            enabled=not args.no_compression,
            compressor=args.compressor,
            shift_rule=args.shift_rule,
            comm_mode=args.comm_mode,
            moe_wire=args.moe_wire,
            act_wire=args.act_wire,
            model_wire=args.model_wire,
            publish_every=args.publish_every,
        )
    )

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'512' if mp else '256'}"
                print(f"=== {tag} ...", flush=True)
                rec = run_one(arch, shape, mp, tcfg, args.out,
                              save_hlo=args.save_hlo,
                              probe_quality=args.probe_quality)
                results.append(rec)
                fname = os.path.join(
                    args.out,
                    f"{arch}_{shape}_{'pod512' if mp else 'pod256'}"
                    f"_{tcfg.compression.comm_mode}.json",
                )
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=2)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']} "
                             f"c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s "
                             f"coll={r['collective_s']:.3f}s")
                elif status == "error":
                    extra = " " + rec["error"][:200]
                print(f"=== {tag}: {status}{extra}", flush=True)
                unresolved = (rec.get("cost_model") or {}).get(
                    "unresolved_whiles") or []
                if sink is not None:
                    from repro import obs

                    sink.emit(obs.event_record(
                        "dryrun_combination", len(results) - 1,
                        arch=arch, shape=shape, status=status,
                        unresolved_while_count=len(unresolved),
                    ))
                if unresolved:
                    print(f"    WARNING: {len(unresolved)} while loop(s) "
                          f"with unresolved trip counts (fell back to 1): "
                          f"{', '.join(unresolved[:4])}"
                          f"{' ...' if len(unresolved) > 4 else ''} — "
                          f"flops/bytes and tuner predictions under-count "
                          f"these loops", flush=True)
                for wrow in rec.get("wires") or ():
                    oh = wrow.get("omega_hat")
                    nm = wrow.get("nmse")
                    print(f"    wire {wrow['name']:<5} "
                          f"{wrow['topology']:<10} {wrow['codec']:<18} "
                          f"{wrow['bytes_per_step']:.3e} B/step  "
                          f"hidden={wrow['overlap_hidden']:.0%}  "
                          f"omega_hat="
                          f"{'-' if oh is None else format(oh, '.3g')}  "
                          f"nmse="
                          f"{'-' if nm is None else format(nm, '.3g')}",
                          flush=True)
                tp = rec.get("tune_preview")
                if tp:
                    mark = ("  (matches configured)"
                            if tp["predicted_choice"]
                            == tp["configured_comm_mode"] else
                            f"  (configured: {tp['configured_comm_mode']})")
                    om = tp.get("omega")
                    print(f"    tune preview: predicted choice "
                          f"{tp['predicted_choice']} "
                          f"@ {tp['predicted_step_s']:.3e}s/step{mark}  "
                          f"[hide: {tp['hide_source']}, omega: "
                          f"{'-' if om is None else format(om, '.3g')} "
                          f"({tp['omega_source']})]",
                          flush=True)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\nDRY-RUN SUMMARY: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if sink is not None:
        from repro import obs

        sink.emit(obs.summary_record("dryrun", ok=n_ok, skipped=n_skip,
                                     errors=n_err))
        sink.close()
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
