"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
with assert_allclose against the pure-jnp oracles (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.natural.kernel import shifted_natural_2d
from repro.kernels.natural.ops import shifted_natural
from repro.kernels.natural.ref import shifted_natural_ref
from repro.kernels.q8ring.kernel import (
    LANE,
    q8_dequant_add_2d,
    q8_quantize_2d,
    q8_quantize_chunk_3d,
    tiles_per_step,
)
from repro.kernels.q8ring.ops import FusedQ8
from repro.kernels.q8ring.ref import q8_dequant_add_ref, q8_quantize_ref
from repro.kernels.topk.kernel import block_topk_2d
from repro.kernels.topk.ops import block_topk
from repro.kernels.topk.ref import block_topk_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_ref
from repro.models.rwkv6 import wkv_scan


# ---------------------------------------------------------------------------
# shifted natural compression
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,block", [(256, 256), (512, 256), (64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shifted_natural_matches_ref(rows, block, dtype):
    key = jax.random.PRNGKey(0)
    kg, kh, ku = jax.random.split(key, 3)
    g = jax.random.normal(kg, (rows, 128), jnp.float32).astype(dtype)
    h = jax.random.normal(kh, (rows, 128), jnp.float32).astype(dtype)
    u = jax.random.uniform(ku, (rows, 128), jnp.float32)
    out = shifted_natural_2d(g, h, u, block_rows=block)
    ref = shifted_natural_ref(g, h, u)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("shape", [(100,), (33, 7), (5, 4, 3, 2), (8192,)])
def test_shifted_natural_arbitrary_shapes(shape):
    key = jax.random.PRNGKey(1)
    g = jax.random.normal(key, shape, jnp.float32)
    h = jnp.zeros(shape, jnp.float32)
    out = shifted_natural(key, g, h)
    assert out.shape == shape
    # with h=0 the output is natural compression: |out| in {0, 2^e, 2^{e+1}}
    nz = np.asarray(out).ravel()
    nz = nz[nz != 0]
    lg = np.log2(np.abs(nz))
    np.testing.assert_allclose(lg, np.round(lg), atol=1e-6)


def test_shifted_natural_unbiased():
    """Monte-Carlo unbiasedness of the kernel as a U(1/8) member."""
    g = jnp.asarray([0.3, -1.7, 5.0, 0.011] * 32, jnp.float32)
    h = jnp.asarray([0.1, -1.0, 4.0, 0.0] * 32, jnp.float32)
    outs = []
    for i in range(512):
        outs.append(shifted_natural(jax.random.PRNGKey(i), g, h))
    mean = np.mean(np.stack(outs), axis=0)
    np.testing.assert_allclose(mean, np.asarray(g), rtol=0.05, atol=0.01)


# ---------------------------------------------------------------------------
# block top-k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,block,k", [(64, 64, 128), (128, 64, 64),
                                          (256, 64, 819), (64, 64, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_topk_matches_ref(rows, block, k, dtype):
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, 128), jnp.float32)
    x = x.astype(dtype)
    out = block_topk_2d(x, k=k, block_rows=block)
    ref = block_topk_ref(x, k=k, block=block)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=1e-6, atol=1e-6,
    )


@pytest.mark.parametrize("q", [0.01, 0.1, 0.5])
def test_block_topk_keep_fraction(q):
    x = jax.random.normal(jax.random.PRNGKey(3), (100_000,), jnp.float32)
    out = np.asarray(block_topk(x, q=q))
    frac = (out != 0).mean()
    assert abs(frac - q) < 0.02, (frac, q)
    # kept values are exactly the input values (no scaling: biased operator)
    kept = out != 0
    np.testing.assert_array_equal(out[kept], np.asarray(x)[kept])


def test_block_topk_contraction():
    """E||C(x)-x||^2 <= (1-delta)||x||^2 with delta = q (per block)."""
    for seed in range(5):
        x = jax.random.normal(jax.random.PRNGKey(seed), (8192,), jnp.float32)
        out = np.asarray(block_topk(x, q=0.2))
        xn = np.asarray(x)
        err = np.sum((out - xn) ** 2)
        assert err <= (1 - 0.2) * np.sum(xn**2) + 1e-4


# ---------------------------------------------------------------------------
# fused q8 ring (quantize + chunk-select + dequant-accumulate)
# ---------------------------------------------------------------------------


#: (rows, block) of the kernel cases: one-tile leaves (28, 8 and 1 rows),
#: and 37 and 44 tiles of 64 rows, which leave the last grid block ragged
Q8_SHAPES = [(8, 8), (64, 8), (64, 64), (96, 32), (1, 1), (37 * 64, 64),
             (44 * 64, 64), (28, 28)]


def _q8_ref(block):
    """The oracles, jitted as the kernels are: the same XLA arithmetic,
    so kernel and oracle agree bit for bit."""
    return (jax.jit(lambda x, u: q8_quantize_ref(x, u, block=block)),
            jax.jit(lambda q, s, a: q8_dequant_add_ref(q, s, a, block=block)))


@pytest.mark.parametrize("rows,block", Q8_SHAPES)
def test_q8_quantize_matches_ref(rows, block):
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, 128)) * 3.0
    u = jax.random.uniform(jax.random.PRNGKey(1), (rows, 128))
    q, s = q8_quantize_2d(x, u, block_rows=block)
    qr, sr = _q8_ref(block)[0](x, u)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (rows // block, 1)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


@pytest.mark.parametrize("rows,block", Q8_SHAPES)
def test_q8_dequant_add_matches_ref_bitwise(rows, block):
    x = jax.random.normal(jax.random.PRNGKey(11), (rows, 128)) * 2.0
    u = jax.random.uniform(jax.random.PRNGKey(12), (rows, 128))
    acc = jax.random.normal(jax.random.PRNGKey(13), (rows, 128))
    quantize, dequant_add = _q8_ref(block)
    q, s = quantize(x, u)
    out = q8_dequant_add_2d(q, s, acc, block_rows=block)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(dequant_add(q, s, acc)))


@pytest.mark.parametrize("rows,block", [(37 * 64, 64), (44 * 64, 64),
                                        (28, 28)])
def test_q8_quantize_chunk_matches_ref(rows, block):
    """The chunk kernel on a ragged chunk tile count (and a one-tile
    chunk), chunk id traced as in the ring loop."""
    chunks = jax.random.normal(jax.random.PRNGKey(14), (4, rows, 128)) * 3.0
    u = jax.random.uniform(jax.random.PRNGKey(15), (rows, 128))
    q, s = jax.jit(
        lambda c, u_, i: q8_quantize_chunk_3d(c, u_, i, block_rows=block)
    )(chunks, u, jnp.int32(2))
    qr, sr = _q8_ref(block)[0](chunks[2], u)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def _pallas_grids(fn, *args):
    """{kernel name: grid} of every pallas_call in fn's jaxpr."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], eqn.params["grid_mapping"].grid
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", p)
                if hasattr(sub, "eqns"):
                    yield from walk(sub)
    return dict(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("n_tiles,block,steps", [
    (37, 64, 3), (44, 64, 3), (4748, 64, 297),   # an embed ring chunk
    (18992, 64, 1187), (1, 28, 1), (1, 1, 1), (300, 8, 3), (8, 256, 2)])
def test_q8_kernels_take_many_tiles_per_grid_step(n_tiles, block, steps):
    """Each grid step covers T scale tiles (16 of 64 rows), not one:
    cdiv(n_tiles, T) steps, on all three kernels."""
    assert steps == -(-n_tiles // tiles_per_step(n_tiles, block))
    f32 = jax.ShapeDtypeStruct((n_tiles * block, LANE), jnp.float32)
    grids = _pallas_grids(
        lambda x, u, c: (
            q8_quantize_2d(x, u, block_rows=block),
            q8_quantize_chunk_3d(c, u, 1, block_rows=block),
            q8_dequant_add_2d(x.astype(jnp.int8),
                              jnp.ones((n_tiles, 1)), x, block_rows=block),
        ),
        f32, f32, jax.ShapeDtypeStruct((4, n_tiles * block, LANE),
                                       jnp.float32),
    )
    assert grids == {"q8_quantize_2d": (steps,),
                     "q8_quantize_chunk_3d": (steps,),
                     "q8_dequant_add_2d": (steps,)}


def test_q8_quantize_chunk_select_matches_2d():
    """The scalar-prefetch chunk variant (the fused ring-hop gather)
    equals quantizing the sliced chunk — for static AND traced ids."""
    chunks = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 128))
    u = jax.random.uniform(jax.random.PRNGKey(3), (16, 128))
    for cid in range(4):
        q, s = q8_quantize_chunk_3d(chunks, u, cid, block_rows=8)
        qr, sr = q8_quantize_2d(chunks[cid], u, block_rows=8)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    qt, st = jax.jit(
        lambda c, u_, i: q8_quantize_chunk_3d(c, u_, i, block_rows=8)
    )(chunks, u, jnp.int32(3))
    qr, sr = q8_quantize_2d(chunks[3], u, block_rows=8)
    np.testing.assert_array_equal(np.asarray(qt), np.asarray(qr))


def test_q8_dequant_add_matches_ref():
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 128)) * 2.0
    u = jax.random.uniform(jax.random.PRNGKey(5), (32, 128))
    acc = jax.random.normal(jax.random.PRNGKey(6), (32, 128))
    q, s = q8_quantize_2d(x, u, block_rows=8)
    out = q8_dequant_add_2d(q, s, acc, block_rows=8)
    ref = q8_dequant_add_ref(q, s, acc, block=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # quantization is tight: |dequant - x| <= one lattice step per tile
    err = np.abs(np.asarray(out - acc) - np.asarray(x))
    step = np.repeat(np.asarray(s)[:, 0], 8)[:, None]
    assert (err <= step + 1e-7).all()


def test_q8_quantize_unbiased():
    """Monte-Carlo unbiasedness of the stochastic rounding (the codec
    must stay a U(omega) member for the DIANA step-size theory)."""
    x = jnp.asarray([0.3, -1.7, 5.0, 0.011] * 32, jnp.float32).reshape(1, 128)
    outs = []
    for i in range(512):
        u = jax.random.uniform(jax.random.PRNGKey(i), x.shape)
        q, s = q8_quantize_2d(x, u, block_rows=1)
        outs.append(np.asarray(q, np.float32) * np.asarray(s)[0, 0])
    mean = np.mean(np.stack(outs), axis=0)
    np.testing.assert_allclose(mean, np.asarray(x), rtol=0.05, atol=0.01)


@pytest.mark.parametrize("shape", [(100,), (33, 7), (5, 4, 3, 2), (8192,),
                                   (), (1,)])
def test_fused_q8_codec_roundtrip_arbitrary_shapes(shape):
    """FusedQ8 decode(encode(x)) stays within one blockwise lattice step
    of x on any shape (incl. scalars) — and the payload is honest int8."""
    x = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32) * 2.0
    c = FusedQ8()
    payload, meta = c.encode(jax.random.PRNGKey(8), x)
    assert payload["q"].dtype == jnp.int8
    assert not jax.tree_util.tree_leaves(meta)  # meta-free: may ride rings
    out = c.decode(payload, meta, jax.ShapeDtypeStruct(x.shape, x.dtype))
    assert out.shape == x.shape and out.dtype == x.dtype
    if x.size:
        bound = np.abs(np.asarray(x)).max() / 127.0 + 1e-6
        assert np.abs(np.asarray(out) - np.asarray(x)).max() <= bound


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,t,h,dk,dv,chunk", [
    (2, 64, 2, 64, 64, 32),
    (1, 128, 4, 64, 64, 128),
    (2, 96, 1, 32, 64, 32),      # rectangular K != V
    (1, 32, 2, 16, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_matches_ref(b, t, h, dk, dv, chunk, dtype):
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    r = jax.random.normal(keys[0], (b, t, h, dk), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (b, t, h, dk), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (b, t, h, dv), jnp.float32).astype(dtype)
    # realistic decay range: w = exp(-exp(x)) in (0,1)
    w = jnp.exp(-jnp.exp(
        jax.random.normal(keys[3], (b, t, h, dk), jnp.float32)
    )).astype(dtype)
    u = jax.random.normal(keys[4], (h, dk), jnp.float32)

    y, s = wkv6(r, k, v, w, u, chunk=chunk)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
    ub = jnp.broadcast_to(u[None], (b, h, dk)).reshape(b * h, dk)
    y_ref, s_ref = wkv6_ref(to_bh(r), to_bh(k), to_bh(v), to_bh(w), ub)
    y_ref = y_ref.reshape(b, h, t, dv).transpose(0, 2, 1, 3)
    s_ref = s_ref.reshape(b, h, dk, dv)

    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=tol, atol=tol)


def test_wkv6_matches_model_scan():
    """Kernel == the model's wkv_scan (same math, different code path)."""
    b, t, h, d = 2, 64, 2, 64
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    r = jax.random.normal(keys[0], (b, t, h, d))
    k = jax.random.normal(keys[1], (b, t, h, d))
    v = jax.random.normal(keys[2], (b, t, h, d))
    w = jnp.exp(-jnp.exp(jax.random.normal(keys[3], (b, t, h, d))))
    u = jax.random.normal(keys[4], (h, d))
    y_kernel, s_kernel = wkv6(r, k, v, w, u, chunk=32)
    y_model, s_model = wkv_scan(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_kernel), np.asarray(s_model),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_chunk_invariance():
    """Chunk size must not change the result (state carry across chunks)."""
    b, t, h, d = 1, 128, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    r = jax.random.normal(keys[0], (b, t, h, d))
    k = jax.random.normal(keys[1], (b, t, h, d))
    v = jax.random.normal(keys[2], (b, t, h, d))
    w = jnp.exp(-jnp.exp(jax.random.normal(keys[3], (b, t, h, d))))
    u = jax.random.normal(keys[4], (h, d))
    y1, s1 = wkv6(r, k, v, w, u, chunk=128)
    y2, s2 = wkv6(r, k, v, w, u, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-5)
