"""Reduce a profiler trace (``*.xplane.pb``) to what the per-layer readers use.

Read with ``jax.profiler.ProfileData`` alone. On a TPU the trace holds one
plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line has one event
per executed HLO instruction and whose ``XLA Modules`` line has one event
per executed program (``jit_<function>(<fingerprint>)``), and a host plane
(``/host:CPU``) with the ``jax.profiler.TraceAnnotation`` spans the harness
opens. All of them share one clock, in nanoseconds from the trace's start.

Everything is clipped to the window span (``bench.window``):

* ``busy_ns``: the union of the ``XLA Ops`` intervals of a chip;
* ``modules``: device time and executions per program, keyed by the module
  name without its fingerprint;
* ``collective_ns``: the union of the intervals of collective instructions
  (all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
  synchronous or as start/done pairs) on the ``XLA Ops`` and ``Async XLA
  Ops`` lines;
* ``ops``: device time per instruction, named ``<module>/<instruction>``;
* ``gaps``: every idle interval of a chip, labelled with the innermost
  harness span open over its midpoint (``-`` where none is).
"""
from __future__ import annotations

import bisect
import gzip
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_FINGERPRINT = re.compile(r"\(\d+\)$")
_INSTR = re.compile(r"^%?([\w.\-]+)")
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?$")


def _union(intervals):
    """Merged, sorted list of ``(start, end)``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def instruction(op_name: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``."""
    m = _INSTR.match(op_name)
    return m.group(1) if m else op_name


def is_collective(op_name: str) -> bool:
    return bool(_COLLECTIVE.match(instruction(op_name)))


def _host_spans(plane):
    spans = []
    for line in plane.lines:
        for ev in line.events:
            if ev.name.startswith(SPAN_PREFIX):
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name))
    return spans


def _label(spans, t):
    """Innermost (latest-starting) span open at ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t < e and name != WINDOW_SPAN and (best is None
                                                   or s > best[0]):
            best = (s, name)
    return best[1] if best else "-"


def reduce_trace(path) -> dict:
    """Reduce the xplane file at ``path`` (``.xplane.pb``, or gzipped
    ``.xplane.pb.gz``); raises if it has no window span or no device
    plane."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            spans += _host_spans(plane)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = windows[0]
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = []
        modules = {}
        for ev in lines.get("XLA Modules", []):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e <= s:
                continue
            name = _FINGERPRINT.sub("", ev.name)
            rec = modules.setdefault(name, {"ns": 0.0, "count": 0})
            rec["ns"] += e - s
            rec["count"] += 1
            mods.append((s, e, name))
        mods.sort()
        starts = [m[0] for m in mods]
        busy, coll, ops = [], [], {}
        for ln in ("XLA Ops", "Async XLA Ops"):
            for ev in lines.get(ln, []):
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
                if e <= s:
                    continue
                if is_collective(ev.name):
                    coll.append((s, e))
                if ln != "XLA Ops":
                    continue
                busy.append((s, e))
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else "-"
                key = f"{mod}/{instruction(ev.name)}"
                ops[key] = ops.get(key, 0.0) + (e - s)
        merged = _union(busy)
        gaps, prev = [], w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                gaps.append((_label(spans, (prev + s) / 2), float(s - prev)))
            prev = max(prev, e)
        devices.append({
            "name": plane.name,
            "busy_ns": _length(merged),
            "modules": modules,
            "collective_ns": _length(_union(coll)),
            "ops": ops,
            "gaps": gaps,
        })
    if not devices:
        raise ValueError(f"{path}: no device plane with an 'XLA Ops' line")
    return {"window_ns": float(w1 - w0), "devices": devices}


def find_xplane(trace_dir) -> Path:
    """The one ``*.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"{trace_dir}: expected one xplane file, found "
                         f"{len(found)}")
    return found[0]


def breakdown(red: dict, n: int = 10) -> dict:
    """The ``n`` device operations that took most time and the ``n``
    longest idle gaps, in seconds, averaged over chips for the ops."""
    devs = red["devices"]
    ops = {}
    for d in devs:
        for k, ns in d["ops"].items():
            ops[k] = ops.get(k, 0.0) + ns / len(devs)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted((g for d in devs for g in d["gaps"]), key=lambda g: -g[1])[:n]
    return {"device_ops": [[k, ns / 1e9] for k, ns in top],
            "idle_gaps": [[lbl, ns / 1e9] for lbl, ns in gaps]}
