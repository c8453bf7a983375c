"""Reduce a profiler trace to the numbers the per-layer metrics read.

`load(path)` reads an ``.xplane.pb`` with `jax.profiler.ProfileData`
into plain lists: device events per chip (ops and modules) and the
harness's own host spans (``bench:*`` trace annotations). `reduce`
works on those lists alone, so the tests can feed it a small recorded
trace or synthetic events:

* busy seconds: the union of the op intervals of each chip, inside the
  traced window, averaged over the chips;
* per-module device seconds (a module is one compiled program; its
  name is the jitted function's, run-suffix stripped);
* per-op device seconds, for the kernels and for the breakdown;
* the longest idle gaps, each named by the innermost harness span that
  holds its midpoint.
"""
from __future__ import annotations

import re

#: lines of a device plane that hold ops, and that hold modules
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
HOST_SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def load(path: str) -> dict:
    """{"chips": {plane: {"ops": [...], "modules": [...]}}, "spans": [...]}
    with every event a (name, start_ns, end_ns) tuple."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ch = chips.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = "ops" if line.name in OP_LINES else \
                    "modules" if line.name in MODULE_LINES else None
                if key:
                    ch[key].extend((short_name(e.name), e.start_ns, e.end_ns)
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(HOST_SPAN_PREFIX))
    return {"chips": chips, "spans": spans}


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction; keep what is
    left of `` = `` and, for a custom call (a Pallas kernel), the
    callee: ``%served_query.4 = f32[256,2] custom-call(...)`` ->
    ``served_query.4:custom-call``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    head = head.lstrip("%")
    return head + ":custom-call" if " custom-call(" in rest else head


def union(intervals, lo: float, hi: float) -> list:
    """Merged, sorted [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_name(name: str) -> str:
    """``jit_place_batch(1234)`` -> ``jit_place_batch``."""
    return re.sub(r"\(\d+\)$", "", name)


def _sum_by(events, lo, hi, key) -> dict:
    out = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            k = key(name)
            out[k] = out.get(k, 0.0) + d * 1e-9
    return out


def reduce(tr: dict, n_gaps: int = 10) -> dict:
    """Window, busy, per-module and per-op seconds and idle gaps."""
    win = [s for s in tr["spans"] if s[0] == WINDOW_SPAN]
    if not win or not tr["chips"]:
        return {}
    lo, hi = win[0][1], win[0][2]
    busy, modules, ops, gaps = [], {}, {}, []
    for ch in tr["chips"].values():
        u = union([(s, e) for _, s, e in ch["ops"] or ch["modules"]], lo, hi)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        for k, v in _sum_by(ch["modules"], lo, hi, module_name).items():
            modules[k] = modules.get(k, 0.0) + v
        for k, v in _sum_by(ch["ops"], lo, hi, lambda n: n).items():
            ops[k] = ops.get(k, 0.0) + v
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = [s for s in tr["spans"] if s[0] != WINDOW_SPAN]
    idle = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]:
        mid = 0.5 * (s + e)
        inner = [h for h in host if h[1] <= mid < h[2]]
        name = min(inner, key=lambda h: h[2] - h[1])[0] if inner \
            else "outside harness calls"
        idle.append((name, (e - s) * 1e-9))
    n = len(tr["chips"])
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / n,
            "chips": n, "modules_s": modules, "ops_s": ops,
            "idle_gaps": idle}


def idle_percent(red) -> float | None:
    """Share of the traced window in which no op ran on the device
    (1 - busy / window, busy averaged over the chips); None without a
    device trace."""
    if not red or not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def module_seconds(red: dict, pattern: str) -> float | None:
    """Device seconds of the modules whose name matches `pattern`,
    summed over chips; None when no module matches."""
    hits = [v for k, v in red.get("modules_s", {}).items()
            if re.search(pattern, k)]
    return sum(hits) if hits else None


def op_seconds(red: dict, pattern: str) -> float | None:
    """Device seconds of the ops whose name matches `pattern`."""
    hits = [v for k, v in red.get("ops_s", {}).items()
            if re.search(pattern, k)]
    return sum(hits) if hits else None


def breakdown(red: dict, n: int = 10) -> dict:
    """The result line's breakdown: the costliest device ops and the
    longest idle gaps by what the harness was doing."""
    ops = sorted(red.get("ops_s", {}).items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.get("idle_gaps", [])[:n]]}
