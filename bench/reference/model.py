"""Plain float32 references: the layers, and the lookup of an architecture.

Straight ``jax.numpy``: no kernels, no chunking tricks, no sharding and
nothing imported from the system under test.  Each layer follows the
equations of the published model as the configuration file states them
(its ``assumed`` list names where the system departs from the paper and
the reference follows the system's stated choice):

* ``attn_block``: a pre-norm decoder block of grouped-query causal
  attention (optional per-head RMS q/k norms, half-split rotary
  embeddings), then a SwiGLU MLP;
* ``mamba_layer``: a pre-norm Mamba-2 layer whose state-space mixer is
  written in its quadratic "dual" form over the whole sequence,
  y_t = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s + D x_t,
  the direct unrolling of S_t = exp(a dt_t) S_{t-1} + B_t (dt_t x_t)^T,
  y_t = C_t^T S_t + D x_t (arXiv:2405.21060, section 6).

How an architecture composes them lives in a module of its own,
``archs/<arch_type>.py``, found by the ``arch_type`` of the model
description: ``init_params(key, m)``, ``logits(params, tokens, m, ein)``
and ``forward_flops_per_token(m, seq)`` (the numerator of ``mfu``, by
``counts.py``'s rules), and for the test suite ``SMOKE``, a small model
description without its ``arch_type``, ``SMOKE_SEQ`` and ``SYSTEM_ARCH``,
the system's arch id it is compared with.  A new architecture adds its
module and edits nothing here.

Matrix products go through a ``Matmul`` policy: float32 at the highest
precision (the reference), or operands rounded to scaled float8 (the
control that a lower-precision path must fail).  The parameter tree has
the same nested-dict layout as the system's, so one set of weights made
from the seed feeds both.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: one module per architecture, ``archs/<arch_type>.py``
ARCHS = pathlib.Path(__file__).resolve().parent / "archs"


# --------------------------------------------------------------------------
# Matrix-product policies
# --------------------------------------------------------------------------


def _scaled_round(x, dtype):
    """Round ``x`` to ``dtype`` under a per-tensor scale that maps its
    largest magnitude onto the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return (x / s).astype(dtype).astype(F32) * s


def _make_fp8_einsum(spec: str):
    """einsum with float8 operands: e4m3 forward operands, e5m2 incoming
    gradients, float32 accumulation (the usual fp8 training recipe)."""
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    spec_da = f"{out},{sb}->{sa}"
    spec_db = f"{sa},{out}->{sb}"

    def prod(s, x, y):
        return jnp.einsum(s, x, y, precision=HIGHEST,
                          preferred_element_type=F32)

    @jax.custom_vjp
    def f(a, b):
        return prod(spec, _scaled_round(a, jnp.float8_e4m3fn),
                    _scaled_round(b, jnp.float8_e4m3fn))

    def fwd(a, b):
        qa = _scaled_round(a, jnp.float8_e4m3fn)
        qb = _scaled_round(b, jnp.float8_e4m3fn)
        return prod(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        qg = _scaled_round(g, jnp.float8_e5m2)
        return prod(spec_da, qg, qb), prod(spec_db, qa, qg)

    f.defvjp(fwd, bwd)
    return f


class Matmul:
    """``ein(spec, a, b)``: every matrix product of the reference."""

    def __init__(self, precision: str = "float32"):
        if precision not in ("float32", "fp8"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.precision = precision
        self._fp8 = {}

    def __call__(self, spec: str, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if self.precision == "float32":
            return jnp.einsum(spec, a, b, precision=HIGHEST)
        if spec not in self._fp8:
            self._fp8[spec] = _make_fp8_einsum(spec)
        return self._fp8[spec](a, b)


# --------------------------------------------------------------------------
# Weights from the seed
# --------------------------------------------------------------------------


def _dims(m: dict):
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return m["d_model"], m["n_heads"], m["n_kv_heads"], hd


def _mamba_dims(m: dict):
    d_inner = m["mamba_expand"] * m["d_model"]
    p = m["mamba_head_dim"]
    return d_inner, d_inner // p, p, m["ssm_state"]


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def attn_block_init(key, m: dict, dtype, n_scaled_layers: int):
    d, h, kv, hd = _dims(m)
    ks = jax.random.split(key, 7)
    out_std = 0.02 / math.sqrt(2 * n_scaled_layers)
    attn = {
        "wq": _normal(ks[0], (d, h * hd), 0.02, dtype),
        "wk": _normal(ks[1], (d, kv * hd), 0.02, dtype),
        "wv": _normal(ks[2], (d, kv * hd), 0.02, dtype),
        "wo": _normal(ks[3], (h * hd, d), out_std, dtype),
    }
    if m.get("qk_norm"):
        attn["q_norm"] = {"scale": jnp.ones((hd,), dtype)}
        attn["k_norm"] = {"scale": jnp.ones((hd,), dtype)}
    f = m["d_ff"]
    return {
        "attn_norm": {"scale": jnp.ones((d,), dtype)},
        "attn": attn,
        "mlp_norm": {"scale": jnp.ones((d,), dtype)},
        "mlp": {
            "w_gate": _normal(ks[4], (d, f), 0.02, dtype),
            "w_up": _normal(ks[5], (d, f), 0.02, dtype),
            "w_down": _normal(ks[6], (f, d), out_std, dtype),
        },
    }


def mamba_layer_init(key, m: dict, dtype):
    """Mamba-2's own initialisation: A in [1, 16], dt log-uniform in
    [1e-3, 1e-1] through the softplus inverse, D = 1, PyTorch's default
    uniform for the depthwise causal conv."""
    d = m["d_model"]
    d_inner, h, _, n = _mamba_dims(m)
    k = m["conv_kernel"]
    conv_dim = d_inner + 2 * n
    ks = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(k)
    dt = jnp.exp(jax.random.uniform(ks[3], (h,), F32, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "norm": {"scale": jnp.ones((d,), dtype)},
        "m2": {
            "w_in": _normal(ks[0], (d, 2 * d_inner + 2 * n + h), 0.02, dtype),
            "conv_w": jax.random.uniform(ks[1], (k, conv_dim), F32, -bound,
                                         bound).astype(dtype),
            "conv_b": jax.random.uniform(ks[2], (conv_dim,), F32, -bound,
                                         bound).astype(dtype),
            "a_log": jnp.log(jax.random.uniform(ks[4], (h,), F32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "d_skip": jnp.ones((h,), F32),
            "norm": {"scale": jnp.ones((d_inner,), dtype)},
            "w_out": _normal(ks[5], (d_inner, d),
                             0.02 / math.sqrt(2 * m["n_layers"]), dtype),
        },
    }


def embed_and_head_init(ks, m: dict, dtype):
    """The embedding (from ``ks[0]``), the final norm, and the output head
    (from ``ks[3]``; nothing when tied to the embedding)."""
    d, v = m["d_model"], m["vocab_size"]
    return {
        "embed": {"table": _normal(ks[0], (v, d), 0.02, dtype)},
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "head": ({} if m.get("tie_embeddings")
                 else {"w": _normal(ks[3], (d, v), 0.02, dtype)}),
    }


# --------------------------------------------------------------------------
# Layers
# --------------------------------------------------------------------------


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, theta):
    """x (B, S, H, dh): rotate the two halves of each head by
    position * theta^(-2i/dh)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]      # (S, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, x, m: dict, ein: Matmul):
    b, s, _ = x.shape
    _, h, kv, hd = _dims(m)
    q = ein("bsd,de->bse", x, p["wq"]).reshape(b, s, h, hd)
    k = ein("bsd,de->bse", x, p["wk"]).reshape(b, s, kv, hd)
    v = ein("bsd,de->bse", x, p["wv"]).reshape(b, s, kv, hd)
    if m.get("qk_norm"):
        q = rmsnorm(q, p["q_norm"]["scale"], m["norm_eps"])
        k = rmsnorm(k, p["k_norm"]["scale"], m["norm_eps"])
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)        # query head j reads kv j//g
    v = jnp.repeat(v, h // kv, axis=2)
    scores = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = ein("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * hd)
    return ein("bse,ed->bsd", out, p["wo"])


def mlp(p, x, ein: Matmul):
    gate = ein("bsd,df->bsf", x, p["w_gate"])
    up = ein("bsd,df->bsf", x, p["w_up"])
    return ein("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"])


def attn_block(p, x, m: dict, ein: Matmul):
    eps = m["norm_eps"]
    x = x + attention(p["attn"], rmsnorm(x, p["attn_norm"]["scale"], eps),
                      m, ein)
    return x + mlp(p["mlp"], rmsnorm(x, p["mlp_norm"]["scale"], eps), ein)


def causal_conv(u, w, b):
    """Depthwise causal conv: out_t = sum_i w_i u_{t+i-(K-1)}, then SiLU."""
    k = w.shape[0]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    t = u.shape[1]
    out = sum(up[:, i: i + t] * w[i].astype(F32) for i in range(k))
    return jax.nn.silu(out + b.astype(F32))


def ssm_dual(x, b_t, c_t, dt, a_log, d_skip, ein: Matmul):
    """x (B,T,H,P); b_t, c_t (B,T,N); dt (B,T,H).  The quadratic form
    of the selective state space recurrence with zero initial state."""
    t = x.shape[1]
    cum = jnp.cumsum(-jnp.exp(a_log)[None, None] * dt, axis=1)   # (B,T,H)
    diff = cum[:, :, None, :] - cum[:, None, :, :]               # (B,T,S,H)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = ein("btn,bsn->bts", c_t, b_t)
    y = ein("btsh,bshp->bthp", cb[..., None] * decay, x * dt[..., None])
    return y + d_skip[None, None, :, None] * x


def mamba_layer(p, x, m: dict, ein: Matmul):
    bsz, t, _ = x.shape
    d_inner, h, pdim, n = _mamba_dims(m)
    q = p["m2"]
    u = rmsnorm(x, p["norm"]["scale"], m["norm_eps"])
    proj = ein("btd,de->bte", u, q["w_in"])
    z = proj[..., :d_inner]
    xbc = causal_conv(proj[..., d_inner: 2 * d_inner + 2 * n],
                      q["conv_w"], q["conv_b"])
    xs = xbc[..., :d_inner].reshape(bsz, t, h, pdim)
    b_t = xbc[..., d_inner: d_inner + n]
    c_t = xbc[..., d_inner + n:]
    dt = jax.nn.softplus(proj[..., 2 * d_inner + 2 * n:] + q["dt_bias"])
    y = ssm_dual(xs, b_t, c_t, dt, q["a_log"], q["d_skip"], ein)
    y = y.reshape(bsz, t, d_inner) * jax.nn.silu(z)
    y = rmsnorm(y, q["norm"]["scale"], m["norm_eps"])
    return x + ein("bte,ed->btd", y, q["w_out"])


def scan(fn, p_stack, x):
    """Run ``x`` through a stack of layers (leading axis), recomputing
    each layer on the backward pass so long sequences fit."""
    body = jax.checkpoint(lambda y, p: (fn(p, y), None))
    return jax.lax.scan(body, x, p_stack)[0]


def embed(params, tokens):
    return params["embed"]["table"][tokens].astype(F32)


def head(params, x, m: dict, ein: Matmul):
    """Logits of the last layer's output ``x``: final norm, then the
    output head (the embedding's transpose when tied)."""
    x = rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])
    if m.get("tie_embeddings"):
        return ein("bsd,vd->bsv", x, params["embed"]["table"])
    return ein("bsd,dv->bsv", x, params["head"]["w"])


# --------------------------------------------------------------------------
# The architecture, by name
# --------------------------------------------------------------------------


def arch(arch_type: str):
    """The module of an architecture, ``archs/<arch_type>.py``."""
    return _load_arch(ARCHS / f"{arch_type}.py")


@functools.cache
def _load_arch(path: pathlib.Path):
    if not path.is_file():
        raise ValueError(f"no reference for arch_type {path.stem!r}: "
                         f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        f"reference_arch_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def init_params(key, m: dict):
    """Random weights for the model described by ``m`` (a configuration
    file's ``model`` object), in its stated dtype, from ``key``."""
    return arch(m["arch_type"]).init_params(key, m)


def logits(params, tokens, m: dict, ein: Matmul):
    """Logits (B, S, V) of ``tokens`` (B, S)."""
    return arch(m["arch_type"]).logits(params, tokens, m, ein)


def row_losses(params, tokens, m: dict, ein: Matmul):
    """Mean next-token cross-entropy of each row of ``tokens`` (B, S)."""
    lg = logits(params, tokens, m, ein)[:, :-1]
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold, axis=-1)
