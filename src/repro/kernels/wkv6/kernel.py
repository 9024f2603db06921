"""RWKV-6 WKV recurrence — Pallas TPU kernel.

Per (batch, head): state S in R^{K x V} (K = V = 64 for Finch);

    y_t = r_t (S + u * k_t v_t^T)
    S  <- diag(w_t) S + k_t v_t^T

The sequence is streamed through VMEM in time-chunks: grid =
(B*H, T/chunk) with the LAST grid dim sequential ("arbitrary"
dimension_semantics on TPU), so the state scratch persists across the
chunk iterations of one (b,h) program while r/k/v/w tiles stream
HBM->VMEM.  All state math is f32 (the recurrence is numerically
delicate under bf16 accumulation); inputs may be bf16.

This is the hardware adaptation of the cuda-style wkv kernel shipped
with RWKV: the GPU version parallelizes over (b,h) thread-blocks with
shared-memory state — here (b,h) maps to the parallel grid dim and the
state lives in VMEM scratch instead.

Within a chunk the time loop is a ``fori_loop`` of rank-1 updates
(K x V outer products): VPU work, deliberately NOT the matmul-chunked
form whose factored decay exponentials overflow for extreme
data-dependent decays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, s_ref,
                 *, chunk: int, n_chunks: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[...].astype(jnp.float32)           # (1, K)

    def step(i, s):
        r = r_ref[0, i, :].astype(jnp.float32)   # (K,)
        k = k_ref[0, i, :].astype(jnp.float32)
        v = v_ref[0, i, :].astype(jnp.float32)
        w = w_ref[0, i, :].astype(jnp.float32)
        kv = k[:, None] * v[None, :]             # (K, V)
        y = jnp.sum((s + u[0][:, None] * kv) * r[:, None], axis=0)  # (V,)
        y_ref[0, i, :] = y.astype(y_ref.dtype)
        return w[:, None] * s + kv

    s = jax.lax.fori_loop(0, chunk, step, s_ref[...])
    s_ref[...] = s

    @pl.when(t_idx == n_chunks - 1)
    def _final():
        s_out_ref[0, :, :] = s


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, *, chunk: int = DEFAULT_CHUNK,
                interpret: bool = True):
    """r,k,w: (BH, T, K); v: (BH, T, V); u: (BH, K).
    Returns (y (BH, T, V) f32, s_final (BH, K, V) f32)."""
    bh, t, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk

    seq_spec = lambda: pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0))
    vseq_spec = pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0))
    u_spec = pl.BlockSpec((1, dk), lambda b, c: (b, 0))
    sfin_spec = pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0))

    y, s_fin = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=(bh, n_chunks),
        in_specs=[seq_spec(), seq_spec(), vseq_spec, seq_spec(), u_spec],
        out_specs=[vseq_spec, sfin_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ) if not interpret else None,
        name="wkv6_pallas",
        interpret=interpret,
    )(r, k, v, w, u)
    return y, s_fin
