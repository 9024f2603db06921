"""Qwen3: a stack of pre-norm decoder blocks (``model.attn_block``:
grouped-query causal attention with per-head RMS q/k norms and
half-split rotary embeddings, then a SwiGLU MLP), then the final norm
and a tied or untied output head."""

from __future__ import annotations

import jax
import jax.numpy as jnp

import counts
from reference import model as RM

SYSTEM_ARCH = "qwen3-0.6b"
SMOKE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256, "qk_norm": True,
         "rope_theta": 1e6, "norm_eps": 1e-5, "tie_embeddings": True,
         "dtype": "float32"}
SMOKE_SEQ = 48


def init_params(key, m: dict):
    dtype = jnp.dtype(m["dtype"])
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[1], m["n_layers"])
    return {
        **RM.embed_and_head_init(ks, m, dtype),
        "blocks": jax.vmap(lambda k: RM.attn_block_init(
            k, m, dtype, m["n_layers"]))(layer_keys),
    }


def logits(params, tokens, m: dict, ein: RM.Matmul):
    x = RM.embed(params, tokens)
    x = RM.scan(lambda p, y: RM.attn_block(p, y, m, ein), params["blocks"], x)
    return RM.head(params, x, m, ein)


def forward_flops_per_token(m: dict, seq: int) -> float:
    n_attn = m["n_layers"]
    fwd = 2.0 * (n_attn * counts.attn_block_matmul_params(m)
                 + counts.head_params(m))
    return fwd + n_attn * counts.attn_flops_per_token(m, seq)
