"""Wire-codec layer tests: encode/decode round-trip identity against the
derived ``__call__``, structural bits accounting (runtime ``wire_bits``
vs the AOT ``aot_wire_bits`` eval_shape path), SimChannel vs MeshChannel
agreement, and payload-size pins for the codec-driven collectives."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    MeshChannel,
    SimChannel,
    aggregation_mode_of,
    collective_payload_scale,
    make_channel,
)
from repro.configs.base import CompressionConfig
from repro.core.compressors import (
    BernoulliP,
    Identity,
    Induced,
    Int8Stochastic,
    NaturalCompression,
    NaturalDithering,
    PackedBits,
    RandK,
    ScaledSign,
    TernGrad,
    TopK,
    Zero,
    aot_wire_bits,
    make_compressor,
    wire_bits,
)

# one representative instance per registry entry
REGISTERED = [
    ("identity", Identity()),
    ("zero", Zero()),
    ("randk", RandK(0.25)),
    ("randk/shared", RandK(0.25, shared_pattern=True)),
    ("bernoulli", BernoulliP(0.3)),
    ("natural_dithering", NaturalDithering(4)),
    ("natural", NaturalCompression()),
    ("terngrad", TernGrad()),
    ("int8", Int8Stochastic()),
    ("topk", TopK(0.25)),
    ("sign", ScaledSign()),
    ("induced", Induced(TopK(0.25), RandK(0.25))),
]
IDS = [n for n, _ in REGISTERED]
OPS = [op for _, op in REGISTERED]


@pytest.fixture(scope="module")
def xvec():
    return jax.random.normal(jax.random.PRNGKey(7), (48,)) * 2.0 + 0.5


@pytest.mark.parametrize("op", OPS, ids=IDS)
def test_roundtrip_matches_derived_call(op, xvec):
    """decode(encode(key, x)) IS __call__(key, x) — for every registered
    codec, on 1-D and 2-D inputs (shape/dtype preserved exactly)."""
    for x in (xvec, xvec.reshape(12, 4)):
        key = jax.random.PRNGKey(3)
        payload, meta = op.encode(key, x)
        dec = op.decode(payload, meta, jax.ShapeDtypeStruct(x.shape, x.dtype))
        out = op(key, x)
        assert dec.shape == x.shape and dec.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(out))


def test_payload_dtypes_honest(xvec):
    """Payloads carry honest wire dtypes: int8 quantized values, packed
    sub-byte index/sign/code fields, f32 scales."""
    key = jax.random.PRNGKey(0)
    p, _ = Int8Stochastic().encode(key, xvec)
    assert p["q"].dtype == jnp.int8 and p["scale"].dtype == jnp.float32

    d = xvec.size
    p, _ = TopK(0.25).encode(key, xvec)
    assert isinstance(p["indices"], PackedBits)
    assert p["indices"].width == math.ceil(math.log2(d))
    assert p["indices"].data.dtype == jnp.int32

    p, _ = RandK(0.25).encode(key, xvec)
    assert isinstance(p["indices"], PackedBits)
    p, meta = RandK(0.25, shared_pattern=True).encode(key, xvec)
    assert "indices" not in p  # pattern implied by the shared seed
    assert meta["indices"].shape == (12,)

    p, _ = TernGrad().encode(key, xvec)
    assert p["tern"].width == 2 and p["tern"].data.dtype == jnp.int8
    p, _ = ScaledSign().encode(key, xvec)
    assert p["sign"].width == 1
    p, _ = NaturalCompression().encode(key, xvec)
    assert p["exp"].width == 8 and p["sign"].width == 1


@pytest.mark.parametrize("op", OPS, ids=IDS)
def test_wire_bits_agrees_with_aot(op, xvec):
    """The AOT ``aot_wire_bits`` (eval_shape of the codec's own encode)
    must equal the structural ``wire_bits`` of a real payload
    (BernoulliP's payload is a random variable; its AOT size is the
    expectation)."""
    d = int(xvec.size)
    payload, _ = op.encode(jax.random.PRNGKey(1), xvec)
    wb = op.wire_bits(payload)
    aot = aot_wire_bits(op, d)
    if isinstance(op, BernoulliP):
        # concrete count: either just the flag, or flag + full vector
        assert float(wb) in (1.0, 1.0 + 32 * d)
        assert aot == op.p * 32 * d + 1.0
    else:
        assert float(wb) == aot, (float(wb), aot)


def test_wire_bits_pins_legacy_formulas():
    """wire_bits / aot_wire_bits ≡ the legacy hand-written per-format
    size formulas for the identity / Rand-K / int8 wire formats."""
    d = 1000
    x = jax.random.normal(jax.random.PRNGKey(2), (d,))
    key = jax.random.PRNGKey(3)

    p, _ = Identity().encode(key, x)
    assert Identity().wire_bits(p) == 32 * d == aot_wire_bits(Identity(), d)

    p, _ = RandK(0.1).encode(key, x)
    assert (RandK(0.1).wire_bits(p) == 100 * (32 + 10)
            == aot_wire_bits(RandK(0.1), d))
    p, _ = RandK(0.1, shared_pattern=True).encode(key, x)
    assert RandK(0.1, shared_pattern=True).wire_bits(p) == 100 * 32

    p, _ = Int8Stochastic().encode(key, x)
    assert Int8Stochastic().wire_bits(p) == 8 * d + 32

    # and the other wire formats keep their legacy sizes too
    assert aot_wire_bits(TopK(0.1), d) == 100 * (32 + 10)
    assert aot_wire_bits(ScaledSign(), d) == d + 32
    assert aot_wire_bits(TernGrad(), d) == 2 * d + 32
    assert aot_wire_bits(NaturalCompression(), d) == 9 * d
    assert aot_wire_bits(NaturalDithering(8), d) == d * (1 + 4) + 32
    assert aot_wire_bits(Zero(), d) == 0


def test_bernoulli_composite_aot_bits():
    """Regression: AOT costing must survive codecs whose wire size is a
    random variable, including nested inside Induced — eval_shape
    payloads report the EXPECTED bits."""
    d = 1000
    b = BernoulliP(0.1)
    assert aot_wire_bits(b, d) == b.p * 32 * d + 1.0
    ind = Induced(c=TopK(0.1), q=b)
    assert aot_wire_bits(ind, d) == (aot_wire_bits(TopK(0.1), d)
                                     + aot_wire_bits(b, d))


def test_ring_stages_reject_meta_codecs():
    """Regression: every forwarded-payload stage (ring hops AND the pod
    psum stage) must reject codecs that keep decoder state in meta —
    the receiver only ever sees the payload."""
    from repro.dist.collectives import _encode_meta_free

    key = jax.random.PRNGKey(0)
    x = jnp.ones((1, 16))
    _encode_meta_free(Int8Stochastic(), key, x)  # meta-free: fine
    with pytest.raises(ValueError, match="meta"):
        _encode_meta_free(RandK(0.25, shared_pattern=True), key, x)


def test_wire_bits_from_eval_shape():
    """Payload costs are computable AOT from shapes alone (eval_shape),
    matching the runtime payload exactly."""
    x = jax.random.normal(jax.random.PRNGKey(4), (257,))
    for op in (RandK(0.1), TopK(0.5), Int8Stochastic(), NaturalCompression()):
        aot, _ = jax.eval_shape(
            op.encode, jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        )
        run, _ = op.encode(jax.random.PRNGKey(5), x)
        assert wire_bits(aot) == float(op.wire_bits(run))


# ---------------------------------------------------------------------------
# Channels
# ---------------------------------------------------------------------------


def _wtree(key, w=4):
    return {
        "a": jax.random.normal(key, (w, 17)),
        "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (w, 3, 5))},
    }


def test_sim_vs_mesh_channel_dense_agree():
    """SimChannel and a dense MeshChannel are interchangeable: identical
    messages, identical aggregate, identical wire bits."""
    key = jax.random.PRNGKey(11)
    wtree = _wtree(key)
    for q in (Identity(), NaturalCompression(), RandK(0.5)):
        sim = SimChannel()
        mesh = make_channel("dense")
        assert isinstance(mesh, MeshChannel)
        m_s, bar_s, b_s = sim.push_mean(q, key, wtree)
        m_m, bar_m, b_m = mesh.push_mean(q, key, wtree)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            (m_s, bar_s), (m_m, bar_m),
        )
        assert float(b_s) == float(b_m)


def test_uplink_bits_are_structural():
    """Channel uplink bits == W x per-message wire_bits (no analytic
    formulas on the live path)."""
    key = jax.random.PRNGKey(12)
    w = 4
    wtree = {"a": jax.random.normal(key, (w, 40))}
    q = RandK(0.25)
    _, bits = SimChannel().uplink(q, key, wtree)
    assert float(bits) == w * aot_wire_bits(q, 40)


def test_mesh_channel_randk_shared_is_codec_driven():
    """The shared-pattern Rand-K aggregation equals mean-of-decoded
    shared-pattern messages (the codec law), and the per-worker payload
    is byte-identical to the K-value wire format."""
    key = jax.random.PRNGKey(13)
    w, d, ratio = 6, 50, 0.2
    k = round(ratio * d)
    wtree = {"a": jax.random.normal(key, (w, d))}
    ch = make_channel("randk_shared", randk_q=ratio)
    out = ch.reduce_mean(key, wtree)

    # reference: every worker encodes with the SAME per-leaf key, master
    # averages the decoded messages exactly
    codec = RandK(q=ratio, shared_pattern=True)
    lk = jax.random.fold_in(key, 0)
    dec = jax.vmap(
        lambda row: codec(lk, row)
    )(wtree["a"])
    np.testing.assert_allclose(
        np.asarray(out["a"]), np.asarray(jnp.mean(dec, axis=0)), rtol=1e-6
    )
    assert int(np.sum(np.asarray(out["a"]) != 0)) <= k

    # byte-identical payload: K f32 values per worker message
    payload, _ = codec.encode(lk, wtree["a"][0])
    assert payload["values"].shape == (k,)
    assert codec.wire_bits(payload) == 32 * k


def test_q8_ring_hop_payload_bytes():
    """The ring forwards exactly the Int8Stochastic payload per hop:
    int8 chunk + one f32 scale (8c + 32 bits)."""
    c = 256
    codec = Int8Stochastic()
    payload, meta = jax.eval_shape(
        codec.encode, jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, c), jnp.float32),
    )
    assert not jax.tree_util.tree_leaves(meta)  # ring needs meta-free codecs
    assert payload["q"].dtype == jnp.int8 and payload["q"].shape == (1, c)
    assert wire_bits(payload) == 8 * c + 32


def test_channel_broadcast_downlink():
    """Model-broadcast through the Channel: identity is exact with 32
    bits/scalar; int8 is close with 8 bits/scalar + scale."""
    key = jax.random.PRNGKey(14)
    tree = {"w": jax.random.normal(key, (8, 8)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (8,))}
    n = 64 + 8
    out, bits = SimChannel().broadcast(Identity(), key, tree)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        out, tree,
    )
    assert float(bits) == 32 * n

    out8, bits8 = SimChannel().broadcast(Int8Stochastic(), key, tree)
    assert float(bits8) == 8 * n + 32 * 2  # one scale per leaf
    for k in ("w", "b"):
        err = np.abs(np.asarray(out8[k]) - np.asarray(tree[k])).max()
        assert err < 0.05 * np.abs(np.asarray(tree[k])).max() + 1e-6


def test_serve_broadcast_params_roundtrip():
    from repro.launch.serve import broadcast_params

    tree = {"w": jax.random.normal(jax.random.PRNGKey(15), (16, 4))}
    out, bits = broadcast_params(tree, "identity")
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert float(bits) == 32 * 64


# ---------------------------------------------------------------------------
# EF21 / config plumbing + the HLO payload model
# ---------------------------------------------------------------------------


def test_ef21_comm_mode_config_plumbing():
    cfg = CompressionConfig(comm_mode="ef21", compressor="topk",
                            compressor_kwargs=(("q", 0.25),))
    assert cfg.effective_shift_rule == "ef21"
    assert cfg.aggregation_mode == "dense"
    assert aggregation_mode_of(cfg) == "dense"
    q, rule = cfg.make()
    from repro.core import EF21Shift, TopK as TopKOp

    assert isinstance(rule, EF21Shift)
    assert isinstance(q, TopKOp)
    ch = make_channel(cfg)
    assert isinstance(ch, MeshChannel) and ch.mode == "dense"


def test_mesh_channel_rejects_unknown_mode():
    with pytest.raises(ValueError):
        MeshChannel(mode="carrier_pigeon")


def test_collective_payload_scale():
    """Only EF21 needs a payload scale (dense HLO lowering of decoded
    sparse messages); the codec-driven collectives are structurally
    honest in the HLO already (see the randk_shared lowering test)."""
    # ef21: the wire carries the contractive codec's payload
    cfg = CompressionConfig(comm_mode="ef21", compressor="topk",
                            compressor_kwargs=(("q", 0.1),))
    s = collective_payload_scale(cfg)["all-reduce"]
    assert 0.1 < s < 0.2  # ~q * (32 + log2 d)/32
    # structurally-honest / disabled modes: no scaling
    assert collective_payload_scale(CompressionConfig(comm_mode="dense")) == {}
    assert collective_payload_scale(
        CompressionConfig(comm_mode="randk_shared", randk_q=0.05)) == {}
    assert collective_payload_scale(
        CompressionConfig(enabled=False, comm_mode="ef21")) == {}


_RANDK_LOWERING = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist.collectives import randk_shared_mean
from repro.launch.hlo_stats import collective_bytes

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
w, d, ratio = 8, 1024, 0.05
k = round(ratio * d)
wtree = {"a": jax.device_put(
    jax.random.normal(jax.random.PRNGKey(0), (w, d)),
    NamedSharding(mesh, P("data")))}
with jax.sharding.set_mesh(mesh):
    hlo = (jax.jit(lambda key, t: randk_shared_mean(key, t, ratio))
           .lower(jax.random.PRNGKey(1), wtree).compile().as_text())
coll = collective_bytes(hlo)
ar = coll["all-reduce"] + coll["reduce-scatter"] + coll["all-gather"]
# the cross-device reduction moves K values, not d: structural honesty
assert 0 < ar <= 4 * 4 * k, (ar, k)   # <= a few K-sized f32 messages
assert ar < 4 * d, (ar, d)            # and strictly below one dense leaf
print("RANDK_LOWERING_OK", ar)
"""


def test_randk_shared_lowering_is_k_sized_subprocess():
    """The codec-driven randk_shared aggregation is structurally honest
    in the HLO: the cross-device collective carries ~K f32 values per
    leaf, NOT the dense d — which is why collective_payload_scale no
    longer rescales it."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", _RANDK_LOWERING],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=repo,
    )
    assert "RANDK_LOWERING_OK" in r.stdout, r.stdout + r.stderr[-3000:]


_HLO = """\
HloModule m

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p0), to_apply=%add
}
"""


def test_hlo_cost_collective_scale():
    from repro.launch.hlo_cost import analyze

    base = analyze(_HLO)
    assert base["collective_bytes"] == 4096
    scaled = analyze(_HLO, collective_scale={"all-reduce": 0.05})
    assert scaled["collective_bytes"] == pytest.approx(4096 * 0.05)
    assert scaled["collective_bytes_structural"] == 4096
    assert scaled["collective_bytes_by_kind"]["all-reduce"] == pytest.approx(
        4096 * 0.05
    )


def test_hlo_cost_gradient_payload_model():
    """Only the gradient-message share is re-charged at the wire
    fraction; dense activation collectives keep their structural
    bytes."""
    from repro.launch.hlo_cost import analyze, apply_gradient_payload_model

    base = analyze(_HLO)  # 4096 structural all-reduce bytes
    out = apply_gradient_payload_model(base, "all-reduce",
                                       message_bytes=1000,
                                       wire_fraction=0.1)
    assert out["collective_bytes_by_kind"]["all-reduce"] == pytest.approx(
        (4096 - 1000) + 1000 * 0.1
    )
    assert out["collective_bytes"] == out["collective_bytes_by_kind"]["all-reduce"]
    # message bytes are capped at the structural total
    out = apply_gradient_payload_model(base, "all-reduce",
                                       message_bytes=10_000_000,
                                       wire_fraction=0.1)
    assert out["collective_bytes"] == pytest.approx(4096 * 0.1)
    # untouched input dict
    assert base["collective_bytes"] == 4096
