"""Operations and bytes the benchmarked work requires, from shapes alone.

These are the numerators of ``mfu`` and of ``q8_roofline``.  They count
what the model and the codec need, not what an implementation happens
to do: recomputation, padding, uniforms and zero accumulators are left
out, so an implementation that does extra work shows a lower share.
An architecture's forward FLOPs are counted by its reference module
(``reference/archs/<arch_type>.py``) from the pieces below.
"""

from __future__ import annotations

import math

from reference import model as RM

LANE = 128


def attn_block_matmul_params(m: dict) -> int:
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // h
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * m["d_ff"]


def attn_flops_per_token(m: dict, seq: int) -> float:
    """Forward QK^T and PV of causal attention, per token of one layer:
    2 products x 2 flops x heads x head_dim x the mean context
    (seq + 1) / 2."""
    h = m["n_heads"]
    hd = m.get("head_dim") or m["d_model"] // h
    return 4.0 * h * hd * (seq + 1) / 2


def ssd_flops_per_token(m: dict) -> float:
    """Forward matmuls of the chunked SSD algorithm (arXiv:2405.21060,
    section 7) per token of one layer, chunk length L from the
    configuration: the causal half of the intra-chunk C.B^T and of its
    product with the inputs, then the state's read-out and update."""
    d_inner = m["mamba_expand"] * m["d_model"]
    p = m["mamba_head_dim"]
    h = d_inner // p
    n = m["ssm_state"]
    ctx = (m["ssd_chunk"] + 1) / 2
    return 2 * ctx * n + 2 * ctx * h * p + 4 * n * h * p


def mamba_matmul_params(m: dict) -> int:
    d = m["d_model"]
    d_inner = m["mamba_expand"] * d
    h = d_inner // m["mamba_head_dim"]
    n = m["ssm_state"]
    return d * (2 * d_inner + 2 * n + h) + d_inner * d


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def model_flops_per_token(m: dict, seq: int) -> float:
    """Training FLOPs per token: 3 x the forward pass (forward, and a
    backward of twice its cost), where the forward is 2 flops per weight
    of every matrix product applied to the token (a shared block once
    for each time it is applied; the output head; not the embedding
    lookup) plus causal attention and any other sequence mixer (the SSD
    matmuls of Mamba-2), as the architecture's module adds them up."""
    return 3.0 * RM.arch(m["arch_type"]).forward_flops_per_token(m, seq)


def _tiles(n_elems: float, block_rows: int) -> int:
    rows = max(1, math.ceil(n_elems / LANE))
    return math.ceil(rows / min(block_rows, rows))


def q8_codec_bytes(d: int, itemsize: int, block_rows: int) -> float:
    """One message through the codec: read the leaf once in its dtype,
    write int8 + one f32 scale per tile, then read those and write the
    decoded leaf in its dtype."""
    payload = d + 4 * _tiles(d, block_rows)
    return 2.0 * (d * itemsize + payload)


def q8_ring_bytes(d: int, n: int, block_rows: int) -> float:
    """One chip's part of the q8 ring all-reduce of a d-element float32
    leaf over n chips, in chunks of d / n.  Reduce-scatter, n - 1 hops:
    read the chunk, write int8 + scales; on receive read those and the
    accumulator, write the accumulator.  All-gather: encode the owned
    chunk once; decode it and each of the n - 1 forwarded payloads
    (read int8 + scales, write the chunk)."""
    if n == 1:
        return 0.0
    c = d / n
    payload = c + 4 * _tiles(c, block_rows)
    scatter = (n - 1) * ((4 * c + payload) + (payload + 8 * c))
    gather = (4 * c + payload) + n * (payload + 4 * c)
    return scatter + gather


def q8_bytes_per_step(leaves, workers_per_chip: int, chips: int,
                      block_rows: int) -> float:
    """Required codec bytes of one training step on one chip: each of
    its workers' messages through the codec, then (on several chips) the
    ring.  ``leaves`` is [(n_elements, itemsize), ...] of the params."""
    total = 0.0
    for d, itemsize in leaves:
        total += workers_per_chip * q8_codec_bytes(d, itemsize, block_rows)
        total += q8_ring_bytes(d, chips, block_rows)
    return total
