"""The trace reduction and the counts behind the per-layer metrics.

Synthetic traces pin the interval arithmetic; the recorded trace
(``testdata/trace_qwen3_dp1.json.gz``: the device operations of at least
1 us and the benchmark host spans of two steps of the one-chip cell on a
TPU v5e) pins that the
readers find what a real trace holds.  No chip and no topology needed.
"""

from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import run  # noqa: E402
import trace_reduce as TR  # noqa: E402

RECORDED = HERE / "testdata" / "trace_qwen3_dp1.json.gz"


def _trace(ops_by_dev, host):
    return {"devices": ops_by_dev, "host": host}


def test_union_and_busy_count_overlaps_once():
    ops = [(0, 10, "a", "s"), (5, 15, "b", "s"), (20, 30, "c", "s")]
    assert TR.union([(s, e) for s, e, _, _ in ops]) == [(0, 15), (20, 30)]
    assert TR.busy_ns(ops, 0, 40) == 25
    assert TR.busy_ns(ops, 8, 25) == 12


def test_idle_share_is_one_minus_busy_over_window():
    tr = _trace({0: [(0, 10, "a", "x"), (20, 30, "b", "x")],
                 1: [(0, 40, "c", "x")]},
                [(0, 5, "bench/input"), (35, 40, "bench/readback")])
    lo, hi = TR.window(tr)
    assert (lo, hi) == (0, 40)
    busy = TR.mean_over_devices(tr, lambda ops: TR.busy_ns(ops, lo, hi))
    assert busy == (20 + 40) / 2
    assert 1 - busy / (hi - lo) == pytest.approx(0.25)


def test_scope_attribution_matches_whole_path_segments():
    ops = [(0, 10, "fusion.1", "jit(_step)/train/grads/dot"),
           (10, 12, "fusion.2", "jit(_step)/train/round/q8"),
           (12, 20, "fusion.3", "jit(_step)/train/grads_extra/x"),
           (20, 25, "fusion.4", "jit(_step)/train/apply/add")]
    assert TR.scope_ns(ops, "train/grads", 0, 30) == 10
    assert TR.scope_ns(ops, "train/round", 0, 30) == 2
    assert TR.scope_ns(ops, "train/apply", 0, 30) == 5


def test_exposed_collective_is_collective_time_without_compute():
    ops = [(0, 10, "fusion.1", "x"),
           (5, 20, "collective-permute-start.3", "x"),
           (15, 18, "fusion.2", "x"),
           (30, 35, "all-reduce.7", "x")]
    # permute 5-20 minus compute 0-10 and 15-18 -> 10-15, 18-20; plus 30-35
    assert TR.exposed_collective_ns(ops, 0, 40) == 5 + 2 + 5
    assert TR.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_idle_gaps_are_named_by_the_host_span():
    tr = _trace({0: [(0, 10, "a", "x"), (30, 40, "b", "x")]},
                [(0, 2, "bench/input"), (12, 28, "bench/readback"),
                 (38, 40, "bench/dispatch")])
    gaps = TR.idle_gaps(tr, 0, 40)
    assert gaps == [["bench/readback", 20 / 1e9]]
    top = TR.top_ops(_trace({0: [(0, 10, "fusion.12", "x"),
                                 (10, 13, "fusion.4", "x"),
                                 (13, 20, "copy.1", "x")]}, []), 0, 40)
    assert top[0] == ["fusion", 13 / 1e9] and top[1] == ["copy", 7 / 1e9]


def test_recorded_trace_reads_every_layer():
    tr = TR.load_json(str(RECORDED))
    lo, hi = TR.window(tr)
    ops = tr["devices"][0]
    busy = TR.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    parts = {s: TR.scope_ns(ops, s, lo, hi)
             for s in ("train/grads", "train/round", "train/apply")}
    assert all(v > 0 for v in parts.values()), parts
    assert parts["train/grads"] > parts["train/round"]
    assert sum(parts.values()) <= busy
    q8 = run.load_reader("q8_roofline")
    assert TR.name_ns(ops, q8.KERNELS, lo, hi) > 0


def test_readers_on_the_recorded_trace():
    tr = TR.load_json(str(RECORDED))
    m = _qwen3()
    leaves = [(28 * 1024 * 3072, 2)] * 3 + [(151936 * 1024, 2)]
    ctx = run.Ctx(tr, TR.window(tr), 2, 2 * 1024, 1, run.load_peaks(
        "TPU v5 lite"), counts.model_flops_per_token(m, 1024),
        counts.q8_bytes_per_step(leaves, 1, 1, 64))
    got = {}
    for name in ("device_idle_share", "input_ms", "grads_ms", "round_ms",
                 "apply_ms", "q8_roofline", "mfu", "collective_exposed_ms"):
        got[name] = run.load_reader(name).read(ctx)
    assert got["collective_exposed_ms"] is None      # one chip
    assert 0 < got["device_idle_share"] < 100
    assert 0 < got["q8_roofline"] < 100 and 0 < got["mfu"] < 100
    step_ms = (ctx.window[1] - ctx.window[0]) / 1e6 / 2
    parts = got["grads_ms"] + got["round_ms"] + got["apply_ms"]
    assert 0 < parts <= step_ms, (got, step_ms)


def _qwen3():
    return {"arch_type": "dense", "n_layers": 28, "d_model": 1024,
            "n_heads": 16, "n_kv_heads": 8, "head_dim": 128, "d_ff": 3072,
            "vocab_size": 151936}


def test_qwen3_flops_are_six_times_matmul_weights_plus_attention():
    seq = 1024
    attn_w = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
    mlp_w = 3 * 1024 * 3072
    weights = 28 * (attn_w + mlp_w) + 1024 * 151936   # tied head
    attention = 28 * 3 * 4 * 16 * 128 * (seq + 1) / 2
    assert counts.model_flops_per_token(_qwen3(), seq) == pytest.approx(
        6 * weights + attention)


def test_q8_bytes_count_the_codec_and_the_ring():
    d = 64 * 128 * 3                      # three whole tiles
    one = counts.q8_codec_bytes(d, 2, 64)
    assert one == 2 * (2 * d + d + 4 * 3)
    assert counts.q8_ring_bytes(d, 1, 64) == 0
    c = d / 4
    payload = c + 4 * 1
    ring = 3 * ((4 * c + payload) + (payload + 8 * c)) \
        + (4 * c + payload) + 4 * (payload + 4 * c)
    assert counts.q8_ring_bytes(d, 4, 64) == pytest.approx(ring)
    assert counts.q8_bytes_per_step([(d, 2)], 1, 4, 64) == pytest.approx(
        one + ring)


def test_every_cell_and_metric_is_found_by_name():
    import json

    spec = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.traffic["chips"] == w["chips"]
        assert set(cell.limits) >= {"loss_gap", "grad_gap", "change_gap"}
        assert {m["name"] for m in cell.per_layer} >= {"mfu", "grads_ms"}
    for m in spec["per_layer"]:
        reader = run.load_reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
    with pytest.raises(run.BenchError):
        run.load_peaks("TPU v0 imaginary")
    assert run.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
