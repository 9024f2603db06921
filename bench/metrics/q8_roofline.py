"""Roofline share of the q8 codec's Pallas kernels: the bytes the codec
must move per step (``counts.q8_bytes_per_step``) at the chip's HBM
bandwidth, over the device time of the kernels, matched by name."""

import re

import trace_reduce as TR

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
KERNELS = re.compile(r"q8_quantize|q8_chunk|q8_dequant")


def read(ctx):
    lo, hi = ctx.window
    ns = TR.mean_over_devices(ctx.trace,
                              lambda ops: TR.name_ns(ops, KERNELS, lo, hi))
    if ns <= 0:
        return None
    need_s = ctx.q8_bytes_per_step * ctx.steps / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / (ns / 1e9)
