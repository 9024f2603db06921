"""The readers of the shift round's phases and of the q8 codec.

Synthetic traces pin the scope matching (plain, wrapped by ``vmap``,
behind a ``shard_map``), the union of intervals and the count of issued
collectives; the recorded traces (``testdata/trace_qwen3_dp1_round.json.gz``
and ``testdata/trace_qwen3_dp4_round.json.gz``: the device operations of
at least 1 us, and every collective, on chip 0 and the benchmark host
spans of two steps of each cell on a TPU v5e) pin that the phases split
the round and that the ring's collectives are counted; a compile of a
tiny train step on the CPU pins that the program carries every scope the
readers look for.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import trace_reduce as TR  # noqa: E402
import trace_scopes as TS  # noqa: E402

PHASES = ("message_ms", "exchange_ms", "shift_ms")
CODEC = ("q8_uniform_ms", "q8_layout_ms")
NEW = PHASES + CODEC + ("exchange_collectives",)
OLD_TRACE = HERE / "testdata" / "trace_qwen3_dp1.json.gz"


def _ctx(ops_by_dev, steps=1, window=(0, 100)):
    tr = {"devices": ops_by_dev,
          "host": [(window[0], window[0] + 1, "bench/input"),
                   (window[1] - 1, window[1], "bench/readback")]}
    return run.Ctx(tr, TR.window(tr), steps, 1, len(ops_by_dev),
                   {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}, 1.0, 1.0)


def _read(name, ctx):
    return run.load_reader(name).read(ctx)


@pytest.mark.parametrize("scope,path,hit", [
    ("q8/uniform", "jit(s)/train/round/message/vmap(q8/uniform)/"
     "jit(_uniform)/max", True),
    ("q8/uniform", "jit(s)/train/round/message/shard_map/"
     "vmap(q8/uniform)/jit(_uniform)/vmap()/add", True),
    ("q8/uniform", "jit(s)/train/round/exchange/bucket_3/shard_map/"
     "q8/uniform/jit(_uniform)/max", True),
    ("q8/layout", "jit(s)/train/round/message/vmap(vmap(q8/layout))/"
     "reshape", True),
    ("q8/layout", "jit(s)/train/round/exchange/bucket_0/shard_map/"
     "q8/layout", True),
    ("q8/layout", "jit(s)/train/round/message/vmap(jit(q8_quantize_2d))/"
     "q8_quantize_2d/squeeze", False),
    ("q8/uniform", "jit(s)/train/round/message/vmap(q8/uniformity)/x",
     False),
    ("q8/uniform", "jit(s)/train/round/message/xq8/uniform/x", False),
    ("train/round/exchange", "jit(s)/train/round/exchange/bucket_0/"
     "shard_map/ppermute", True),
    ("train/round/shift", "jit(s)/train/round/shift_extra/add", False),
])
def test_scope_re_matches_whole_segments_plain_or_wrapped(scope, path, hit):
    assert bool(TS.scope_re(scope).search(path)) is hit


def test_phase_readers_take_the_union_averaged_over_chips():
    r = "jit(s)/train/round"
    dev0 = [(0, 10, "fusion.1", "jit(s)/train/grads/dot"),
            (10, 30, "q8_quantize_2d.3", f"{r}/message/vmap(jit(q8))/x"),
            (20, 40, "fusion.2", f"{r}/message/vmap(q8/layout)/reshape"),
            (40, 50, "collective-permute-start.1",
             f"{r}/exchange/bucket_0/shard_map/ppermute"),
            (45, 60, "fusion.3", f"{r}/exchange/bucket_0/shard_map/div"),
            (60, 70, "multiply_add_fusion", f"{r}/shift/add"),
            (70, 72, "fusion.4", f"{r}/jit(_threefry_split)/slice")]
    dev1 = [(0, 10, "fusion.1", f"{r}/message/sub"),
            (10, 20, "fusion.2", f"{r}/shift/mul"),
            (20, 30, "fusion.3", f"{r}/shift/resync")]
    ctx = _ctx({0: dev0, 1: dev1}, steps=2)
    # chip 0: message 10-40 = 30, exchange 40-60 = 20, shift 10;
    # chip 1: message 10, exchange 0, shift 20; per step: / 2
    assert _read("message_ms", ctx) == pytest.approx((30 + 10) / 2 / 2 / 1e6)
    assert _read("exchange_ms", ctx) == pytest.approx((20 + 0) / 2 / 2 / 1e6)
    assert _read("shift_ms", ctx) == pytest.approx((10 + 20) / 2 / 2 / 1e6)
    round_ms = _read("round_ms", ctx)
    parts = sum(_read(n, ctx) for n in PHASES)
    # the round's remainder here is the key split, 2 ns on chip 0
    assert round_ms - parts == pytest.approx(2 / 2 / 2 / 1e6)


def test_codec_readers_find_wrapped_scopes_and_count_overlaps_once():
    r = "jit(s)/train/round"
    ops = [(0, 10, "add_maximum_fusion",
            f"{r}/message/vmap(q8/uniform)/jit(_uniform)/max"),
           (5, 12, "fusion.9", f"{r}/message/shard_map/vmap(q8/uniform)/"
                               "jit(_uniform)/vmap()/add"),
           (20, 26, "add_maximum_fusion.2",
            f"{r}/exchange/bucket_1/shard_map/q8/uniform/jit(_uniform)/max"),
           (30, 40, "reshape.1", f"{r}/message/vmap(q8/layout)/reshape"),
           (40, 44, "pad_convert_fusion",
            f"{r}/exchange/bucket_0/shard_map/q8/layout/pad"),
           (50, 60, "maximum_bitcast_fusion",
            f"{r}/message/vmap(jit(q8_quantize_2d))/q8_quantize_2d/squeeze")]
    ctx = _ctx({0: ops})
    assert _read("q8_uniform_ms", ctx) == pytest.approx((12 + 6) / 1e6)
    assert _read("q8_layout_ms", ctx) == pytest.approx((10 + 4) / 1e6)


def test_exchange_collectives_count_each_issued_collective_once():
    x = "jit(s)/train/round/exchange/bucket_0/shard_map"
    ops = [(10, 12, "collective-permute-start.1", f"{x}/ppermute"),
           (12, 30, "collective-permute-done.1", f"{x}/ppermute"),
           (30, 32, "collective-permute.4", f"{x}/ppermute"),
           (32, 40, "all-reduce.2", f"{x}/psum"),
           (40, 50, "q8_quantize_chunk_3d.3", f"{x}/jit(q8)/pallas_call"),
           (50, 55, "all-gather-start.1", "jit(s)/train/apply/gather"),
           (150, 152, "collective-permute-start.7", f"{x}/ppermute")]
    two = _ctx({0: ops, 1: ops[:2]}, steps=2, window=(0, 100))
    # chip 0: start + synchronous permute + all-reduce = 3; chip 1: 1;
    # the apply's gather and the permute after the window are not counted
    assert _read("exchange_collectives", two) == pytest.approx((3 + 1) / 2 / 2)
    bare = _ctx({0: [(0, 5, "fusion.1", f"{x}/div")]})
    assert _read("exchange_collectives", bare) == 0


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_without_their_scope(name):
    """On a trace of a program without the scopes (the recorded trace of
    the step before they were added) every new reader reads nothing."""
    tr = TR.load_json(str(OLD_TRACE))
    ctx = run.Ctx(tr, TR.window(tr), 2, 2 * 1024, 1,
                  run.load_peaks("TPU v5 lite"), 1.0, 1.0)
    assert run.load_reader(name).read(ctx) is None
    assert run.load_reader("round_ms").read(ctx) > 0


def test_new_readers_are_declared_as_the_benchmark_names_them():
    spec = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name in NEW:
        m, reader = per_layer[name], run.load_reader(name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
        assert set(m["workloads"]) <= cells
    four = "qwen3-0.6b.dp4.q8ring-overlap"
    assert per_layer["exchange_ms"]["workloads"] == [four]
    assert per_layer["exchange_collectives"]["workloads"] == [four]


#: recorded traces of the change that added the scopes: (file, chips)
RECORDED = {
    "qwen3-0.6b.dp1.q8-diana": ("trace_qwen3_dp1_round.json.gz", 1),
    "qwen3-0.6b.dp4.q8ring-overlap": ("trace_qwen3_dp4_round.json.gz", 4),
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_phases_split_the_round_on_the_recorded_traces(cell):
    name, chips = RECORDED[cell]
    tr = TR.load_json(str(HERE / "testdata" / name))
    ctx = run.Ctx(tr, TR.window(tr), 2, 2 * 1024, chips,
                  run.load_peaks("TPU v5 lite"), 1.0, 1.0)
    got = {n: _read(n, ctx) for n in NEW + ("round_ms",)}
    four = chips > 1
    # one worker's mean compiles to nothing: no operation under exchange
    assert (got["exchange_ms"] is not None) is four
    assert (got["exchange_collectives"] is not None) is four
    parts = got["message_ms"] + (got["exchange_ms"] or 0.0) + got["shift_ms"]
    assert abs(parts - got["round_ms"]) <= 0.05 * got["round_ms"], got
    assert 0 < got["q8_uniform_ms"] < got["message_ms"] + (got["exchange_ms"]
                                                            or 0.0)
    assert 0 < got["q8_layout_ms"] < got["message_ms"] + (got["exchange_ms"]
                                                           or 0.0)
    if four:
        # 13 leaves x 6 ring hops x (int8 block, scales), as compiled
        assert got["exchange_collectives"] == 156


SCOPES_SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
import trace_reduce as TR
from repro.configs import get_config
from repro.configs.base import CompressionConfig, TrainConfig
from repro.data.tokens import TokenStream
from repro.launch.mesh import make_host_mesh, n_workers
from repro.launch.train import (batch_pspecs, init_placed_state,
                                jit_train_step, named_shardings)

cfg = get_config("qwen3-0.6b").with_(n_layers=2, d_model=128, n_heads=4,
                                     n_kv_heads=2, head_dim=32, d_ff=256,
                                     vocab_size=512)
out = {}
for mode, chips in (("dense", 1), ("q8_ring_overlap", 4)):
    comp = CompressionConfig(compressor="q8_block", shift_rule="diana",
                             comm_mode=mode)
    tcfg = TrainConfig(total_steps=4, warmup_steps=1, compression=comp)
    mesh = make_host_mesh(jax.devices()[:chips])
    w = n_workers(mesh)
    state, sh = init_placed_state(jax.random.PRNGKey(0), cfg, tcfg, mesh, w)
    batch = TokenStream(cfg, 32, 2 * w).batch(0)
    batch = jax.device_put(batch, named_shardings(batch_pspecs(batch, mesh),
                                                  mesh))
    hlo = (jit_train_step(cfg, tcfg, mesh, w, sh).lower(state, batch)
           .compile().as_text())
    out[mode] = sorted(set(TR.op_scopes(hlo).values()))
print(json.dumps(out))
"""


def test_train_step_carries_every_scope_the_readers_read():
    """DIANA + ``q8_block`` on ``dense`` (W = 1) and on ``q8_ring_overlap``
    (W = 4 over four host devices): the compiled step's HLO metadata
    holds the phases under ``train/round``, the codec's scopes in the
    messages (and, on the ring, in the exchange), and the ring's
    collectives under ``exchange``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", SCOPES_SCRIPT, str(HERE),
         str(HERE.parent / "src")], env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    scopes = json.loads(out.stdout.strip().splitlines()[-1])

    def found(mode, *parts):
        pats = [TS.scope_re(p) for p in parts]
        return any(all(p.search(s) for p in pats) for s in scopes[mode])

    for mode in ("dense", "q8_ring_overlap"):
        for phase in ("message", "shift"):
            assert found(mode, f"train/round/{phase}"), (mode, phase)
        for codec in ("q8/uniform", "q8/layout"):
            assert found(mode, "train/round/message", codec), (mode, codec)
        assert found(mode, "train/round/message", "q8_quantize_2d")
        assert found(mode, "train/round/message", "q8_dequant_add_2d")
    # the mean over one worker compiles to nothing on the CPU: dense
    # leaves no operation under ``exchange`` there
    ring = "q8_ring_overlap"
    assert found(ring, "train/round/exchange")
    for codec in ("q8/uniform", "q8/layout"):
        assert found(ring, "train/round/exchange", "shard_map", codec), codec
    assert found(ring, "train/round/exchange", "bucket_0", "ppermute")
    assert found(ring, "train/round/exchange", "q8_quantize_chunk_3d")
    assert not found("dense", "train/round/exchange", "ppermute")
