"""Serving: batched single-token decode against sharded caches.

``build_serve_step`` returns the pure decode function; ``decode_specs``
builds ShapeDtypeStruct stand-ins for (params, state, tok, pos) used by
the dry-run.  ``broadcast_params`` routes the model-broadcast (the
downlink direction of the framework) through the same ``repro.comm``
Channel the trainer uses for its uplink, so a quantized weight
broadcast (int8 / natural) shares the codec and its structural wire
accounting with the rest of the system.  KV caches are sharded batch-over-("pod","data") and
SEQUENCE-over-"model": with GQA kv-head counts (8) below the model-axis
size (16), head sharding cannot absorb the model axis — sequence sharding
keeps per-device cache bytes ~C/256 and lowers the softmax over the
sharded key dim to small all-reduces (max + sum), which is the standard
TPU serving layout.

Decode-shape policy (DESIGN.md §Arch-applicability): decode_32k uses the
full-length cache; long_500k uses the native O(1) state for ssm, and a
sliding-window (8192) rolling cache for every attention-bearing arch;
the audio enc-dec skips long_500k.
"""

from __future__ import annotations

import argparse
import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig
from repro.dist import params_pspecs, validate_pspecs
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M

tmap = jax.tree_util.tree_map

LONG_WINDOW = 8192


def serving_config(cfg: ModelConfig, shape_name: str) -> ModelConfig:
    """Arch variant actually served for a given decode shape."""
    if shape_name == "long_500k" and cfg.arch_type != "ssm":
        if cfg.arch_type == "audio":
            raise ValueError("long_500k is skipped for the audio enc-dec "
                             "(see DESIGN.md)")
        return cfg.with_(sliding_window=LONG_WINDOW)
    return cfg


def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window > 0:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def build_serve_step(cfg: ModelConfig):
    def serve_step(params, state, tok, pos):
        logits, state = M.decode_step(params, cfg, tok, state, pos)
        return logits, state
    return serve_step


def broadcast_params(params, compressor: str = "identity", *,
                     key: Optional[jax.Array] = None, channel=None,
                     comm_mode: str = "sim"):
    """Model-broadcast through the Channel downlink.

    The params pytree is encoded leaf-wise with the named codec and
    decoded on the receiving side — ``identity`` is the exact (f32)
    broadcast, ``int8`` / ``natural`` give a quantized weight broadcast
    at 8-9 bits/scalar.  Returns ``(params_received, wire_bits)`` with
    bits computed structurally from the actual payloads.

    ``comm_mode`` builds the channel when none is passed — through
    ``make_channel``, so an unresolved ``"auto"`` sentinel or a typo'd
    mode fails HERE with the same named-accepted-modes error every
    other channel boundary raises, not as a confusing shape error
    downstream.
    """
    from repro.comm import make_channel
    from repro.core.compressors import make_compressor

    channel = channel if channel is not None else make_channel(comm_mode)
    q = make_compressor(compressor)
    key = jax.random.PRNGKey(0) if key is None else key
    return channel.broadcast(q, key, params)


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------


def decode_state_pspecs(state_shapes, mesh):
    """Cache sharding: batch over data axes, sequence over 'model'.

    Leaf conventions (see models.model.make_decode_state):
      attention k/v        (L, B, C, KV, Dh) -> P(None, data, 'model', None, None)
      mla ckv/kr           (L, B, C, r)      -> P(None, data, 'model', None)
      kpos                 (L, C)            -> replicated
      ssm / rwkv states    (L, B, ...)       -> batch over data
      cross-attn xkv       (L, B, S_src, KV, Dh) -> seq over 'model'
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    data = data_axes if data_axes else None

    def one(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        last = names[-1]
        if last == "kpos":
            return P()
        if last in ("k", "v", "ckv", "kr"):
            # (L, B, C, ...) — cache: seq (axis 2) over model
            dims = [None, data, "model"] + [None] * (leaf.ndim - 3)
            return P(*dims[: leaf.ndim])
        # recurrent states / conv tails: (L, B, ...)
        dims = [None, data] + [None] * (leaf.ndim - 2)
        return P(*dims[: leaf.ndim])

    specs = jax.tree_util.tree_map_with_path(one, state_shapes)
    return validate_pspecs(state_shapes, specs, mesh)


def decode_specs(cfg: ModelConfig, seq_len: int, global_batch: int,
                 dtype_params=None):
    """ShapeDtypeStructs for (params, state, tok, pos) — dry-run inputs."""
    cache_len = cache_len_for(cfg, seq_len)
    enc_len = seq_len if cfg.is_encoder_decoder else 0
    params = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    state = jax.eval_shape(
        lambda: M.make_decode_state(cfg, global_batch, cache_len, enc_len)
    )
    tok = jax.ShapeDtypeStruct((global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return params, state, tok, pos


# ---------------------------------------------------------------------------
# CLI: serve a smoke model with batched requests on the host
# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--broadcast-compressor", "--broadcast_compressor",
                    dest="broadcast_compressor", default="identity",
                    help="codec for the model-broadcast downlink "
                         "(identity = exact, int8/natural = quantized)")
    ap.add_argument("--serve_fleet", "--serve-fleet", dest="serve_fleet",
                    type=int, default=0,
                    help="N > 0: run the trainer->fleet delta-stream demo "
                         "with N continuous-batching replicas instead of "
                         "the single-host greedy loop")
    ap.add_argument("--model_wire", "--model-wire", dest="model_wire",
                    default="q8",
                    help="model-downlink codec flag for the fleet demo "
                         "(dense = lossless bit-delta, q8/natural/topk/...)")
    ap.add_argument("--publish_every", "--publish-every",
                    dest="publish_every", type=int, default=2,
                    help="trainer steps between delta publishes")
    ap.add_argument("--stale_k", "--stale-k", dest="stale_k", type=int,
                    default=4, help="staleness bound K (steps behind the "
                                    "trainer) before a dense resync")
    ap.add_argument("--trainer_steps", "--trainer-steps",
                    dest="trainer_steps", type=int, default=6,
                    help="trainer steps to run in the fleet demo")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.serve_fleet > 0:
        import json

        from repro.serving import run_fleet_demo

        stats = run_fleet_demo(
            args.arch, n_replicas=args.serve_fleet,
            model_wire=args.model_wire, publish_every=args.publish_every,
            stale_k=args.stale_k, steps=args.trainer_steps,
            n_requests=2 * args.serve_fleet, gen_len=args.gen_len,
        )
        print(json.dumps(stats, indent=2, default=float))
        print(f"fleet[{args.serve_fleet}x {args.arch}] wire={args.model_wire}:"
              f" {stats['bytes_fraction']:.3f} of dense bytes/publish,"
              f" max staleness {stats['max_staleness']} (K={args.stale_k}),"
              f" {stats['resyncs']} resyncs,"
              f" {stats['tokens_served']} tokens served")
        return stats

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.with_(dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    params, bcast_bits = broadcast_params(
        params, args.broadcast_compressor, key=jax.random.PRNGKey(17)
    )
    print(f"model broadcast [{args.broadcast_compressor}]: "
          f"{float(bcast_bits) / 8e6:.2f} MB on the wire")
    cache_len = args.prompt_len + args.gen_len
    enc_len = args.prompt_len if cfg.is_encoder_decoder else 0
    state = M.make_decode_state(cfg, args.batch, cache_len, enc_len)

    step = jax.jit(build_serve_step(cfg))
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (args.batch, 1), 0, cfg.vocab_size)
    t0 = time.time()
    out = []
    for t in range(args.prompt_len + args.gen_len):
        logits, state = step(params, state, toks, jnp.int32(t))
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(toks[:, 0])
    dt = time.time() - t0
    total = args.batch * (args.prompt_len + args.gen_len)
    print(f"{args.arch}: {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s batched greedy)")
    return jnp.stack(out, 1)


if __name__ == "__main__":
    main()
