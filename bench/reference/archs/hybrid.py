"""The system's own hybrid (``models/model.py``, arch ``hybrid``): periods
of ``attn_every`` Mamba-2 layers (``model.mamba_layer``), each period
followed by one application of a shared attention + SwiGLU block at
``d_model`` (``model.attn_block``, the same weights every time), then the
final norm and the output head.

This is the system's guess at Zamba2 (arXiv:2411.15242), not the
published block: Zamba2's shared block reads the concatenation of the
hidden state and the embedding, carries per-invocation LoRA adapters and
a GELU-gated MLP, and alternates ``num_mem_blocks`` shared blocks.  No
cell runs it; it is here so that the system's hybrid keeps a plain
reference to be compared with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import counts
from reference import model as RM

SYSTEM_ARCH = "zamba2-1.2b"
SMOKE = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256, "ssm_state": 16,
         "attn_every": 2, "rwkv_head_dim": 16, "conv_kernel": 4,
         "norm_eps": 1e-5, "rope_theta": 1e4, "qk_norm": False,
         "tie_embeddings": False, "dtype": "float32",
         # sizes the system fixes in code
         "mamba_expand": 2, "mamba_head_dim": 16, "ssd_chunk": 128}
#: under the system's SSD chunk of 128, so its sequential scan runs
SMOKE_SEQ = 64


def init_params(key, m: dict):
    dtype = jnp.dtype(m["dtype"])
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[1], m["n_layers"])
    return {
        **RM.embed_and_head_init(ks, m, dtype),
        "blocks": jax.vmap(lambda k: RM.mamba_layer_init(k, m, dtype))(
            layer_keys),
        "shared_attn": RM.attn_block_init(ks[2], m, dtype, m["n_layers"]),
    }


def logits(params, tokens, m: dict, ein: RM.Matmul):
    x = RM.embed(params, tokens)
    period = m["attn_every"]
    shared = jax.checkpoint(lambda p, y: RM.attn_block(p, y, m, ein))
    for lo in range(0, m["n_layers"], period):
        seg = jax.tree_util.tree_map(lambda a: a[lo: lo + period],
                                     params["blocks"])
        x = RM.scan(lambda p, y: RM.mamba_layer(p, y, m, ein), seg, x)
        if lo + period <= m["n_layers"]:
            x = shared(params["shared_attn"], x)
    return RM.head(params, x, m, ein)


def forward_flops_per_token(m: dict, seq: int) -> float:
    """Every Mamba-2 layer's matmuls and SSD, and the shared block once
    for each time it is applied."""
    n_attn = m["n_layers"] // m["attn_every"]
    fwd = 2.0 * (m["n_layers"] * counts.mamba_matmul_params(m)
                 + n_attn * counts.attn_block_matmul_params(m)
                 + counts.head_params(m))
    fwd += n_attn * counts.attn_flops_per_token(m, seq)
    return fwd + m["n_layers"] * counts.ssd_flops_per_token(m)
