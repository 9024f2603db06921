"""Transformer substrate: norms, RoPE, GQA attention with chunked
(flash-style) softmax and rolling KV caches, SwiGLU MLP, embeddings.

Everything is module-free pure JAX: ``init_*`` builds a nested-dict
param tree, ``*_apply`` consumes it.  Parameter *names* are what the
sharding rules in ``repro.dist.sharding`` match on — keep them stable.

Shape conventions:  x (B, S, D);  q (B, S, H, Dh);  k/v (B, S, KV, Dh);
caches (B, C, KV, Dh) with write cursor ``pos`` (rolling when the config
uses a sliding window).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def shard_hint(x, *spec):
    """Best-effort sharding constraint on an activation.

    Per-dim entries:  a mesh axis name (or tuple) pins that dim to the
    axis;  ``None`` leaves the dim UNCONSTRAINED (propagation decides —
    crucial under vmap, where forcing replication would fight the mapped
    worker axis);  the string ``"rep"`` forces the dim replicated (e.g.
    gathering the key sequence once before streamed attention).
    No-op unless the ambient mesh has a "model" axis wider than one
    device (CPU smoke tests and the data-parallel trainer have none);
    a constraint the mesh rejects raises.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or dict(mesh.shape).get("model", 1) == 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dims = tuple(
        P.UNCONSTRAINED if d is None else (None if d == "rep" else d)
        for d in spec
    )
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*dims)))


def wire_boundary(wire, key, x, e):
    """Pipeline-boundary activation compression: pass a block output
    through a transport wire (codec round-trip, straight-through on the
    backward pass), threading the per-wire error-feedback shift ``e``.
    Thin indirection so layer code never imports the comm package —
    ``wire`` is a ``repro.comm.transport.Wire`` (anything with ``.send``).
    Returns ``(y, e_new)``.
    """
    return wire.send(key, x, e)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def init_rmsnorm(d: int, cfg: ModelConfig) -> Params:
    return {"scale": jnp.ones((d,), pdtype(cfg))}


def rmsnorm(p: Params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------


def rope_freqs(dh: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: (..., S) int32."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                       # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, Dh/2)
    cos = jnp.cos(ang)[..., None, :]                  # (..., S, 1, Dh/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Chunked (flash-style) attention core
# --------------------------------------------------------------------------


def _grouped_scores(q, k):
    """q (B,Sq,KV,G,Dh) x k (B,Sk,KV,Dh) -> (B,KV,G,Sq,Sk) without
    materializing repeated KV heads."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k)


def _out_proj(out, wo):
    """(B,S,H,dh) x (H*dh, D) — plain matmul against the 2-D weight."""
    b, s, h, dh = out.shape
    return out.reshape(b, s, h * dh) @ wo


def chunked_attention(
    q, k, v, *,
    causal: bool,
    q_offset,                 # int or () int32 array: absolute pos of q[0]
    k_positions,              # (Sk,) absolute positions of keys (for mask)
    k_valid=None,             # (B, Sk) or (Sk,) bool — False = masked out
    window: int = 0,
    q_chunk: int = 512,       # kept for config compat: = key-chunk size
):
    """Grouped-query attention with ONLINE softmax, scanned over KEY
    chunks (flash-attention recurrence): running (max, sum, out)
    accumulators; the live score block is (B, KV, G, Sq, kc) — never the
    full (Sq, Sk) matrix.  The query sequence dim is the one the mesh
    shards ("model"-axis sequence parallelism), so keeping Sq intact and
    streaming keys makes per-shard transients ~Sq_shard * kc.

    Decode (Sq == 1) takes the single-block path so a key-sharded cache
    lowers to one masked softmax with small cross-shard reductions.
    Softmax in f32.
    """
    b, sq, h, dh = q.shape
    dv = v.shape[-1]
    kv = k.shape[2]
    g = h // kv
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, sq, kv, g, dh)
    kpos = k_positions.astype(jnp.int32)
    qpos = q_offset + jnp.arange(sq, dtype=jnp.int32)

    def block(qc, kc_, vc_, kpos_c, kvalid_c):
        """One key block: masked scores -> (scores, mask) in f32."""
        s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc_,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((sq, kc_.shape[1]), bool)
        if causal:
            mask &= kpos_c[None, :] <= qpos[:, None]
        if window and window > 0:
            mask &= kpos_c[None, :] > (qpos[:, None] - window)
        if kvalid_c is not None:
            kvld = kvalid_c if kvalid_c.ndim == 2 else kvalid_c[None]
            m = mask[None, None, None, :, :] & kvld[:, None, None, None, :]
        else:
            m = mask[None, None, None, :, :]
        return jnp.where(m, s, -1e30)

    kc = min(q_chunk, sk)
    if sq == 1 or sk <= kc:
        # single block: decode path / short sequences
        s = block(qg, k, v, kpos, k_valid)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
        return o.reshape(b, sq, h, dv)

    pad = (-sk) % kc
    n_chunks = (sk + pad) // kc
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=2**30)
        if k_valid is not None:
            kvld2 = k_valid if k_valid.ndim == 2 else k_valid[None]
            k_valid = jnp.pad(kvld2, ((0, 0), (0, pad)))

    kb = k.reshape(b, n_chunks, kc, kv, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_chunks, kc, kv, dv).transpose(1, 0, 2, 3, 4)
    kpb = kpos.reshape(n_chunks, kc)
    kvb = (
        k_valid.reshape(k_valid.shape[0], n_chunks, kc).transpose(1, 0, 2)
        if k_valid is not None else None
    )

    m0 = jnp.full((b, kv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    o0 = jnp.zeros((b, kv, g, sq, dv), jnp.float32)

    def body(carry, xs):
        m, l, o = carry
        if kvb is None:
            kc_, vc_, kp_ = xs
            kvld_c = None
        else:
            kc_, vc_, kp_, kvld_c = xs
        s = block(qg, kc_, vc_, kp_, kvld_c)          # (B,KV,G,Sq,kc)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        # p @ v in the value dtype (bf16): halves the probability-block
        # HBM traffic and puts the contraction on the bf16 MXU path;
        # the (m, l, o) accumulators stay f32 (§Perf-3).
        o = o * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v.dtype), vc_,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, o), None

    xs = (kb, vb, kpb) if kvb is None else (kb, vb, kpb, kvb)
    (m, l, o), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, o0), xs)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    # (B,KV,G,Sq,dv) -> (B,Sq,H,dv)
    out = o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
    return out.astype(v.dtype)


# --------------------------------------------------------------------------
# GQA attention layer
# --------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> Params:
    """Projection weights are stored 2-D (d, H*dh): (a) the fused head
    dim always divides the "model" mesh axis regardless of head COUNT
    (40 heads won't 16-shard; 40*128 will), and (b) the layer-scan body
    sees a plain matmul — no per-iteration transpose of the stacked
    3-D weights (§Perf-3)."""
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    sc = 0.02
    p = {
        "wq": _normal(ks[0], (d, h * dh), pdtype(cfg), sc),
        "wk": _normal(ks[1], (d, kv * dh), pdtype(cfg), sc),
        "wv": _normal(ks[2], (d, kv * dh), pdtype(cfg), sc),
        "wo": _normal(ks[3], (h * dh, d), pdtype(cfg), sc / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), pdtype(cfg))
        p["bk"] = jnp.zeros((kv * dh,), pdtype(cfg))
        p["bv"] = jnp.zeros((kv * dh,), pdtype(cfg))
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(dh, cfg)
        p["k_norm"] = init_rmsnorm(dh, cfg)
    return p


def _qkv(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kv, dh)
    v = v.reshape(b, s, kv, dh)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_apply(p, x, cfg: ModelConfig):
    """Full-sequence (train/prefill) causal self-attention."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    # sequence parallelism: queries stay sharded over "model" on seq;
    # keys/values must be whole.  Adaptive gather (§Perf-2): for GQA
    # (2*kv*dh < d) gather the small k/v AFTER projection; for MHA-like
    # heads (k+v as big as x) gather x ONCE before the projections —
    # halves the per-layer all-gather volume for kv=40 archs.
    gather_x = 2 * cfg.n_kv_heads * cfg.head_dim >= cfg.d_model
    if gather_x:
        x = shard_hint(x, None, "rep", None)
    q, k, v = _qkv(p, x, cfg, positions)
    q = shard_hint(q, None, "model", None, None)
    if not gather_x:
        k = shard_hint(k, None, "rep", None, None)
        v = shard_hint(v, None, "rep", None, None)
    out = chunked_attention(
        q, k, v,
        causal=True,
        q_offset=jnp.int32(0),
        k_positions=jnp.arange(s, dtype=jnp.int32),
        window=cfg.sliding_window,
        q_chunk=cfg.attn_q_chunk,
    )
    return _out_proj(out, p["wo"])


def attention_prefill(p, x, cfg: ModelConfig, cache_len: int):
    """Prefill: same as apply, but also returns the KV cache laid out for
    decode, plus the next write position."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _qkv(p, x, cfg, positions)
    out = chunked_attention(
        q, k, v,
        causal=True,
        q_offset=jnp.int32(0),
        k_positions=jnp.arange(s, dtype=jnp.int32),
        window=cfg.sliding_window,
        q_chunk=cfg.attn_q_chunk,
    )
    kvd = k.dtype
    kc = jnp.zeros((b, cache_len, *k.shape[2:]), kvd)
    vc = jnp.zeros((b, cache_len, *v.shape[2:]), kvd)
    kpos = jnp.full((b, cache_len), -1, jnp.int32)
    if cache_len >= s:
        kc = kc.at[:, :s].set(k)
        vc = vc.at[:, :s].set(v)
        kpos = kpos.at[:, :s].set(jnp.arange(s, dtype=jnp.int32)[None])
    else:  # rolling window: keep the last cache_len tokens, ring layout
        tail_k = k[:, s - cache_len:]
        tail_v = v[:, s - cache_len:]
        tail_p = jnp.arange(s - cache_len, s, dtype=jnp.int32)
        slot = tail_p % cache_len
        kc = kc.at[:, slot].set(tail_k)
        vc = vc.at[:, slot].set(tail_v)
        kpos = kpos.at[:, slot].set(tail_p[None])
    cache = {"k": kc, "v": vc, "kpos": kpos}
    return _out_proj(out, p["wo"]), cache


def attention_decode(p, x, cfg: ModelConfig, cache, pos):
    """One-token decode. ``pos`` — scalar int32 absolute position; cache is
    a ring buffer of length C (C >= sliding window, or full seq)."""
    b = x.shape[0]
    positions = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1))
    q, k, v = _qkv(p, x, cfg, positions)
    c = cache["k"].shape[1]
    slot = (pos % c).astype(jnp.int32)
    kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    kpos = jax.lax.dynamic_update_slice_in_dim(
        cache["kpos"],
        jnp.broadcast_to(pos.astype(jnp.int32), (b, 1)), slot, axis=1,
    )
    valid = kpos >= 0                               # (B, C) per-slot
    # shared decode clock: the written position at a slot is identical
    # across batch rows (or -1 where a row was admitted later and the
    # stale entry was invalidated) — max over B recovers it for the
    # causal mask; k_valid handles per-row validity.
    shared_pos = jnp.max(kpos, axis=0)
    out = chunked_attention(
        q, kc, vc,
        causal=True,
        q_offset=pos.astype(jnp.int32),
        k_positions=jnp.where(shared_pos >= 0, shared_pos, jnp.int32(2**30)),
        k_valid=valid,
        window=cfg.sliding_window,
        q_chunk=1,
    )
    y = _out_proj(out, p["wo"])
    return y, {"k": kc, "v": vc, "kpos": kpos}


def make_attention_cache(cfg: ModelConfig, b: int, cache_len: int, dtype):
    return {
        "k": jnp.zeros((b, cache_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((b, cache_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "kpos": jnp.full((b, cache_len), -1, jnp.int32),  # per-slot validity
    }


# --------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# --------------------------------------------------------------------------


def init_cross_attention(key, cfg: ModelConfig) -> Params:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _normal(ks[0], (d, h * dh), pdtype(cfg), 0.02),
        "wk": _normal(ks[1], (d, kv * dh), pdtype(cfg), 0.02),
        "wv": _normal(ks[2], (d, kv * dh), pdtype(cfg), 0.02),
        "wo": _normal(ks[3], (h * dh, d), pdtype(cfg), 0.02 / math.sqrt(2 * cfg.n_layers)),
    }


def cross_attention_kv(p, enc_out, cfg: ModelConfig):
    b, s, _ = enc_out.shape
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"]).reshape(b, s, kv, dh)
    v = (enc_out @ p["wv"]).reshape(b, s, kv, dh)
    return k, v


def cross_attention_apply(p, x, kv_pair, cfg: ModelConfig, enc_valid=None):
    k, v = kv_pair
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    out = chunked_attention(
        q, k, v,
        causal=False,
        q_offset=jnp.int32(0),
        k_positions=jnp.arange(k.shape[1], dtype=jnp.int32),
        k_valid=enc_valid,
        q_chunk=cfg.attn_q_chunk,
    )
    return _out_proj(out, p["wo"])


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _normal(ks[0], (d, f), pdtype(cfg), 0.02),
        "w_up": _normal(ks[1], (d, f), pdtype(cfg), 0.02),
        "w_down": _normal(ks[2], (f, d), pdtype(cfg), 0.02 / math.sqrt(2 * cfg.n_layers)),
    }


def mlp_apply(p, x):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard_hint(h, None, None, "model")
    return h @ p["w_down"]


# --------------------------------------------------------------------------
# Embeddings / head
# --------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig) -> Params:
    p = {"table": _normal(key, (cfg.vocab_size, cfg.d_model), pdtype(cfg), 0.02)}
    return p


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def init_lm_head(key, cfg: ModelConfig) -> Params:
    if cfg.tie_embeddings:
        return {}
    return {"w": _normal(key, (cfg.d_model, cfg.vocab_size), pdtype(cfg), 0.02)}


def lm_head(p, x, cfg: ModelConfig, emb_params):
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, emb_params["table"])
    return jnp.einsum("bsd,dv->bsv", x, p["w"])


def softmax_xent(logits, targets, valid=None):
    """Cross-entropy in f32 over (possibly model-sharded) vocab.  Uses
    take_along_axis for the gold logit — no (B,S,V) one-hot materializes
    (matters at vocab 152k x 1M tokens)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if valid is None:
        return jnp.mean(nll)
    w = valid.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
