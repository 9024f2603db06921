"""Property-based tests (hypothesis) on the system's invariants:
compressor contracts (Definitions 1-3), shifted-compressor algebra
(Lemma 1), induced compressor (Lemma 3), and sharding-spec validity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compressors import (
    BernoulliP,
    Identity,
    Induced,
    Int8Stochastic,
    NaturalCompression,
    NaturalDithering,
    RandK,
    ScaledSign,
    TernGrad,
    TopK,
    shifted,
)

UNBIASED = [
    RandK(0.25), BernoulliP(0.5), NaturalCompression(),
    NaturalDithering(8), TernGrad(), Int8Stochastic(), Identity(),
]
CONTRACTIVE = [TopK(0.25), ScaledSign(), Identity()]

vec = st.lists(
    st.floats(min_value=-100, max_value=100, allow_nan=False,
              width=32).filter(lambda v: v == 0 or abs(v) > 1e-6),
    min_size=8, max_size=64,
)


def _mc(op, x, n=400, seed=0):
    outs = jnp.stack([
        op(jax.random.PRNGKey(seed + i), x) for i in range(n)
    ]).astype(jnp.float32)
    return outs


@pytest.mark.parametrize("op", UNBIASED, ids=lambda o: type(o).__name__)
@settings(max_examples=8, deadline=None, derandomize=True)
@given(data=vec)
def test_unbiasedness(op, data):
    """E C(x) = x within Monte-Carlo error."""
    x = jnp.asarray(data, jnp.float32)
    outs = _mc(op, x)
    mean = jnp.mean(outs, axis=0)
    sd = jnp.std(outs, axis=0) / np.sqrt(outs.shape[0])
    err = np.abs(np.asarray(mean - x))
    # third term: rare-event coords may see ZERO firings in n samples
    # (sample sd = 0), e.g. TernGrad's p = |x_i|/max|x|; cover them with
    # a max-scaled slack.
    bound = (6 * np.asarray(sd) + 0.02 * np.abs(np.asarray(x))
             + 0.25 * float(np.max(np.abs(np.asarray(x)))) / np.sqrt(outs.shape[0])
             + 1e-3)
    assert (err <= bound).all(), (err - bound).max()


@pytest.mark.parametrize("op", UNBIASED, ids=lambda o: type(o).__name__)
@settings(max_examples=8, deadline=None, derandomize=True)
@given(data=vec)
def test_variance_bound(op, data):
    """E||C(x)-x||^2 <= omega ||x||^2 (Def. 2b) within MC error."""
    x = jnp.asarray(data, jnp.float32)
    d = x.size
    outs = _mc(op, x, n=300)
    sq = jnp.sum((outs - x) ** 2, axis=1)
    mean_sq = float(jnp.mean(sq))
    se = float(jnp.std(sq)) / np.sqrt(outs.shape[0])
    omega = op.omega(d)
    bound = omega * float(jnp.sum(x**2))
    assert mean_sq <= bound * (1 + 1e-5) + 4 * se + 1e-5, (mean_sq, bound)


@pytest.mark.parametrize("op", CONTRACTIVE, ids=lambda o: type(o).__name__)
@settings(max_examples=10, deadline=None, derandomize=True)
@given(data=vec)
def test_contraction(op, data):
    """||C(x)-x||^2 <= (1-delta)||x||^2 (Def. 1) — deterministic ops."""
    x = jnp.asarray(data, jnp.float32)
    d = x.size
    out = op(jax.random.PRNGKey(0), x)
    err = float(jnp.sum((out - x) ** 2))
    bound = (1 - op.delta(d)) * float(jnp.sum(x**2))
    assert err <= bound + 1e-4 * max(bound, 1.0)


@settings(max_examples=10, deadline=None, derandomize=True)
@given(data=vec, hdata=vec)
def test_shifted_compressor_lemma1(data, hdata):
    """Q_h(x) = h + Q(x-h): E = x; variance scales with ||x-h||^2."""
    d = min(len(data), len(hdata))
    x = jnp.asarray(data[:d], jnp.float32)
    h = jnp.asarray(hdata[:d], jnp.float32)
    op = NaturalCompression()
    outs = jnp.stack([
        shifted(op, h, jax.random.PRNGKey(i), x) for i in range(300)
    ])
    mean = jnp.mean(outs, axis=0)
    err = np.abs(np.asarray(mean - x))
    sd = np.asarray(jnp.std(outs, axis=0)) / np.sqrt(300)
    # rare-event slack: coords whose stochastic rounding fires ~never in
    # 300 draws have sample sd = 0 but true bias up to half a lattice gap
    scale = max(float(np.max(np.abs(np.asarray(x)))),
                float(np.max(np.abs(np.asarray(h)))), 1.0)
    assert (err <= 6 * sd + 0.02 * np.abs(np.asarray(x))
            + 0.25 * scale / np.sqrt(300) + 1e-3).all()
    # variance bound: omega * ||x-h||^2
    sq = float(jnp.mean(jnp.sum((outs - x) ** 2, axis=1)))
    bound = op.omega(d) * float(jnp.sum((x - h) ** 2))
    assert sq <= bound * 1.3 + 1e-4


def test_shift_exactness_at_shift():
    """Q_h(h) = h exactly — the defining property of the shifted class:
    variance vanishes at the SHIFT, not at the origin."""
    h = jnp.asarray([0.5, -2.0, 3.25, 1e-3] * 8, jnp.float32)
    for op in UNBIASED:
        out = shifted(op, h, jax.random.PRNGKey(0), h)
        np.testing.assert_allclose(np.asarray(out), np.asarray(h), rtol=1e-6)


@settings(max_examples=10, deadline=None, derandomize=True)
@given(data=vec)
def test_induced_compressor_lemma3(data):
    """C_ind = C + Q(x - C(x)) is unbiased with omega*(1-delta)."""
    x = jnp.asarray(data, jnp.float32)
    d = x.size
    op = Induced(c=TopK(0.5), q=RandK(0.5))
    outs = _mc(op, x, n=300)
    mean = jnp.mean(outs, axis=0)
    sd = np.asarray(jnp.std(outs, axis=0)) / np.sqrt(300)
    err = np.abs(np.asarray(mean - x))
    assert (err <= 6 * sd + 0.02 * np.abs(np.asarray(x)) + 1e-3).all()
    # variance strictly better than Q alone (statistically)
    assert op.omega(d) <= RandK(0.5).omega(d) + 1e-9


# ---------------------------------------------------------------------------
# sharding-spec validity
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None, derandomize=True)
@given(
    dims=st.lists(st.integers(min_value=1, max_value=64), min_size=1,
                  max_size=4),
)
def test_validate_pspecs_always_divides(dims):
    """After validation, every sharded dim divides its mesh axis product."""
    import os
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import validate_pspecs
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = make_mesh((1, 1), ("data", "model"))
    shapes = [jax.ShapeDtypeStruct(tuple(dims), jnp.float32)]
    specs = [P(*( ["model"] + [None] * (len(dims) - 1) ))]
    fixed = validate_pspecs(shapes, specs, mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for leaf, sp in zip(shapes, fixed):
        for size, ax in zip(leaf.shape, tuple(sp)):
            if ax is not None:
                axs = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axs:
                    n *= sizes[a]
                assert size % n == 0
