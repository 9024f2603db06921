"""Reduce a profiler trace to the benchmark's per-layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two things: each chip's device operations, as ``(start_ns, end_ns,
name, scope)`` with ``name`` the HLO instruction and ``scope`` the
name-scope path it was traced under (``jit(...)/train/grads/...``, from
the compiled program's HLO metadata), and the benchmark's own host spans
(``bench/input``, ``bench/dispatch``, ``bench/readback``) as
``(start_ns, end_ns, name)``.  Host and device events share one clock.

The functions below work on those plain lists, so the arithmetic can be
checked on a small recorded trace without a chip.  Every time is the
length of a union of intervals, never a plain sum, so operations that
overlap on one chip are not counted twice.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

HOST_SPAN_PREFIX = "bench/"
#: operations that move data between chips; their "-start"/"-done"
#: halves and synchronous forms all match
COLLECTIVE_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ppermute|psum)")
_SUFFIX_RE = re.compile(r"[.\-_]\d+$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                       r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled program's HLO text:
    the name-scope path (``jit(train_step)/train/grads/...``) each
    instruction was traced under.  The TPU trace names an operation by
    its instruction only, so this is how its scope is found."""
    return dict(_INSTR_RE.findall(hlo_text))


def module_name(hlo_text: str) -> str:
    m = re.search(r"HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


def _instr(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _device_index(plane_name: str):
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(trace_dir: str, scopes: dict) -> dict:
    """``{"devices": {index: [(start, end, name, scope), ...]},
    "host": [(start, end, name), ...]}`` from the newest trace under
    ``trace_dir``.  An operation's scope is its op_name in
    ``scopes[<program>]`` (see ``op_scopes``) for the program that was
    running it, else that program's name."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                          for ev in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            ops = devices.setdefault(idx, [])
            for ev in lines.get("XLA Ops", []):
                name = _instr(ev.name)
                k = bisect.bisect_right(starts, ev.start_ns) - 1
                prog = mods[k][2] if k >= 0 and ev.start_ns < mods[k][1] \
                    else ""
                table = scopes.get(prog)
                scope = table.get(name, prog) if table is not None else prog
                ops.append((ev.start_ns, ev.end_ns, name, scope))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    for ops in devices.values():
        ops.sort()
    host.sort()
    return {"devices": devices, "host": host}


def save(reduced: dict, path: str) -> None:
    """Write a loaded trace as JSON (the format of the recorded test
    trace)."""
    with open(path, "w") as f:
        json.dump({"devices": {str(k): v for k, v in
                               reduced["devices"].items()},
                   "host": reduced["host"]}, f)


def load_json(path: str) -> dict:
    """A trace written by ``save`` (gzipped when the name ends in .gz)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


# --------------------------------------------------------------------------
# Interval arithmetic
# --------------------------------------------------------------------------


def union(intervals):
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Disjoint intervals ``a`` minus disjoint intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo, hi):
    """Intervals of [lo, hi] that ``merged`` leaves uncovered."""
    return subtract([(lo, hi)], clip(merged, lo, hi))


# --------------------------------------------------------------------------
# Readings
# --------------------------------------------------------------------------


def window(trace: dict):
    """The traced window: from the first benchmark host span to the end
    of the last one (the last step's read-back)."""
    host = trace["host"]
    if not host:
        raise ValueError("trace holds no benchmark host spans")
    return host[0][0], max(e for _, e, _ in host)


def busy_ns(ops, lo, hi) -> int:
    return length(clip(union((s, e) for s, e, _, _ in ops), lo, hi))


def scope_ns(ops, scope: str, lo, hi) -> int:
    """Device time of the operations traced under name scope ``scope``."""
    return length(clip(union((s, e) for s, e, _, sc in ops
                             if f"/{scope}/" in f"/{sc}/"), lo, hi))


def name_ns(ops, pattern, lo, hi) -> int:
    """Device time of the operations whose name or scope matches the
    compiled regex ``pattern``."""
    return length(clip(union((s, e) for s, e, n, sc in ops
                             if pattern.search(n) or pattern.search(sc)),
                       lo, hi))


def exposed_collective_ns(ops, lo, hi) -> int:
    """Time a collective runs and no other operation does."""
    coll = union((s, e) for s, e, n, _ in ops if COLLECTIVE_RE.search(n))
    comp = union((s, e) for s, e, n, _ in ops if not COLLECTIVE_RE.search(n))
    return length(clip(subtract(coll, comp), lo, hi))


def mean_over_devices(trace: dict, fn) -> float:
    vals = [fn(ops) for ops in trace["devices"].values()]
    return sum(vals) / len(vals)


def top_ops(trace: dict, lo, hi, k: int = 10):
    """``[[name, seconds], ...]``: the k operation kinds (numeric suffix
    dropped) with the most device time in the window, per chip."""
    tot = {}
    for ops in trace["devices"].values():
        for s, e, n, _ in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = _SUFFIX_RE.sub("", n)
                tot[key] = tot.get(key, 0) + d
    n_dev = max(1, len(trace["devices"]))
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n_dev / 1e9] for name, ns in rows]


def idle_gaps(trace: dict, lo, hi, k: int = 10):
    """``[[host span, seconds], ...]``: the k longest device-idle gaps
    of the first chip, each named by the benchmark host span that
    overlaps it most (``"none"`` when the host was in none)."""
    ops = trace["devices"][min(trace["devices"])]
    holes = gaps(union((s, e) for s, e, _, _ in ops), lo, hi)
    named = []
    for s, e in holes:
        best, best_ns = "none", 0
        for hs, he, hn in trace["host"]:
            ov = min(e, he) - max(s, hs)
            if ov > best_ns:
                best, best_ns = hn, ov
        named.append([best, (e - s) / 1e9])
    return sorted(named, key=lambda r: -r[1])[:k]
