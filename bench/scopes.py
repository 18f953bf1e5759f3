"""Per-layer device time from the named scopes inside the programs, and the
device's idle time split by the program's own host spans.

Read from the profiler's ``*.xplane.pb`` with a reader of the protobuf wire
format written here (stdlib only): ``jax.profiler.ProfileData`` does not
expose the event metadata's stats, and the one this needs is ``tf_op``,
the JAX ``op_name`` path of each ``XLA Ops`` event, e.g.
``jit(train_step)/vmap(train.grad)/transpose(jvp())/while/body/mul:``.

Everything is clipped to the window span (``bench.window``):

* ``programs``: per program (``XLA Modules`` name without its fingerprint)
  its executions and the self time of its ops by layer. An op's self time
  is its duration less that of the ops nested inside it on the same line
  (a ``while`` holds its body's ops), so the self times add up to the busy
  time and nothing is counted twice. An op is charged by the first
  ``op_name`` of its ``;``-list to the first layer of ``LAYERS`` whose
  names all appear on the path, as a whole component or inside a
  transform's parentheses (``vmap(train.grad)``); the last component, the
  primitive, never matches. A collective that XLA left without an
  ``op_name`` is charged to ``EXCHANGE``: on a v5e the pod all-gather is
  rewritten into an all-reduce of a zero-padded buffer that keeps none,
  and the sync's exchange is the only collective the programs run. What
  no layer takes is charged to ``-``; the sub-layers of ``SUBLAYERS`` are
  charged beside the layers, not instead.
* ``idle``: per chip, the device's idle time split at the boundaries of the
  program's host spans (``hfl.*``), each piece charged to the innermost
  span open over it (``-`` where none is), and the number of ``hfl.run``
  spans (one per HFL period) that start in the window. The profiler puts
  the host's and the device's events on one clock only to within about a
  millisecond, as long as the gaps it splits, so the spans are first moved
  onto the device's clock (``clock_offset_ns``, how far back): a program cannot start
  before the span that launches it (``hfl.train`` a program with ``train.*``
  scopes, ``hfl.sync`` one with ``sync.*``), so where a program that
  starts on an idle device appears to start before its launch span, the
  spans are moved back until the least such lag is 0.

Scope names are matched as the program writes them: a renamed scope reads
as missing, it is never followed.
"""
from __future__ import annotations

import bisect
import gzip
import os
import re
from pathlib import Path

from bench.trace_reduce import is_collective

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "hfl."
RUN_SPAN = "hfl.run"
# the span that launches a program whose scopes start with the key
LAUNCH_SPANS = {"train": "hfl.train", "sync": "hfl.sync"}
# a launch span further than this from a program's start did not start it
LAUNCH_REACH_PS = 5_000_000_000
# a program that starts closer than this to the end of the one before was
# queued behind it, not started by its launch
QUEUED_PS = 100_000_000
UNSCOPED = "-"
# (layer, names that must all be on the op's path); the first match wins
LAYERS = (
    ("train.optimizer", ("train.optimizer",)),
    ("train.backward", ("train.grad", "transpose")),
    ("train.forward", ("train.grad",)),
    ("sync.select", ("sync.select",)),
    ("sync.compact", ("sync.compact",)),
    ("sync.exchange", ("sync.exchange",)),
    ("sync.merge", ("sync.merge",)),
)
SUBLAYERS = (("attention", ("attention",)),)
EXCHANGE = "sync.exchange"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_TOKEN = re.compile(r"[^()]+")


# ---------------------------------------------------------------------------
# Protobuf wire format (XSpace, tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------


def _fields(buf, start=0, end=None):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields, raw bytes for fixed-width ones."""
    i, n = start, len(buf) if end is None else end
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield key >> 3, v


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map(v):
    """One map entry: (key, value message)."""
    key = val = None
    for f, x in _fields(v):
        if f == 1:
            key = x
        elif f == 2:
            val = x
    return key, val


def _stat(v, stat_names):
    """XStat -> (name, its ``str_value``, or the name a ``ref_value``
    refers to; None for a number)."""
    mid, val = None, None
    for f, x in _fields(v):
        if f == 1:
            mid = x
        elif f == 5:
            val = _str(x)
        elif f == 7:
            val = stat_names.get(x, "")
    return stat_names.get(mid, ""), val


def _planes(data):
    """Each XPlane as (name, lines, event metadata, stat names); a line is
    (name, [(metadata id, start ps, duration ps)]), an event metadata
    entry (name, [stats messages])."""
    for f, pv in _fields(data):
        if f != 1:
            continue
        name, lines, emeta, snames = "", [], {}, {}
        for pf, v in _fields(pv):
            if pf == 2:
                name = _str(v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                k, m = _map(v)
                mname, mstats = "", []
                for mf, x in _fields(m):
                    if mf == 2:
                        mname = _str(x)
                    elif mf == 5:
                        mstats.append(x)
                emeta[k] = (mname, mstats)
            elif pf == 5:
                k, m = _map(v)
                snames[k] = next((_str(x) for mf, x in _fields(m)
                                  if mf == 2), "")
        yield name, [_line(v) for v in lines], emeta, snames


def _line(v):
    name, ts_ns, raw = "", 0, []
    for f, x in _fields(v):
        if f == 2:
            name = _str(x)
        elif f == 3:
            ts_ns = x
        elif f == 4:
            raw.append(x)
    events = []
    for ev in raw:
        mid = off = dur = 0
        for f, x in _fields(ev):
            if f == 1:
                mid = x
            elif f == 2:
                off = x
            elif f == 3:
                dur = x
        events.append((mid, ts_ns * 1000 + off, dur))
    return name, events


# ---------------------------------------------------------------------------
# Layers of an op path
# ---------------------------------------------------------------------------


def path_names(op_name: str) -> frozenset:
    """The scope and transform names on the path of ``op_name``, the
    primitive (the last component) left out."""
    first = op_name.split(";")[0]
    if ":" in first:
        first = first[:first.rindex(":")]
    comps = first.split("/")[:-1]
    return frozenset(t for c in comps for t in _TOKEN.findall(c))


def layer_of(op_name: str, layers=LAYERS) -> str:
    names = path_names(op_name)
    for layer, need in layers:
        if names.issuperset(need):
            return layer
    return UNSCOPED


def op_layer(op_name: str | None, event: str, layers=LAYERS) -> str:
    """The layer of an ``XLA Ops`` event named ``event`` whose op path is
    ``op_name`` (None where XLA left it none)."""
    if op_name:
        return layer_of(op_name, layers)
    return EXCHANGE if is_collective(event) else UNSCOPED


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------


def _read(path) -> bytes:
    data = Path(path).read_bytes()
    return gzip.decompress(data) if str(path).endswith(".gz") else data


def _self_times(ops, w0, w1):
    """``ops`` [(start, end, payload)] on one line -> [(self ps, payload)],
    each clipped to [w0, w1): its length less its direct children's."""
    def clip(s, e):
        return max(0, min(e, w1) - max(s, w0))

    out, stack = [], []
    for s, e, p in sorted(ops, key=lambda o: (o[0], -o[1])):
        # the parent is the innermost open op that holds all of this one
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            stack.pop()
        rec = [clip(s, e), p]
        if stack:
            stack[-1][2][0] -= rec[0]
        out.append(rec)
        stack.append((s, e, rec))
    return out


def _innermost(spans, t0, t1):
    """Name of the latest-starting span covering all of [t0, t1)."""
    best = None
    for s, e, name in spans:
        if s <= t0 and t1 <= e and (best is None or s > best[0]):
            best = (s, name)
    return best[1] if best else UNSCOPED


def _clock_offset(spans, mods, kinds):
    """Ps to take off the host spans' times to put them on the device's
    clock: minus the most negative lag from the start of a launch span to
    the start of a program it started on an idle device (``kinds`` names
    each program's role, ``LAUNCH_SPANS`` the role's span; the nearest such
    span within ``LAUNCH_REACH_PS``). 0 where no lag is negative."""
    launches = {k: sorted(s for s, _, n in spans if n == name)
                for k, name in LAUNCH_SPANS.items()}
    worst, prev_end = 0, None
    for s, e, mod in mods:
        starts = launches.get(kinds.get(mod), [])
        if starts and (prev_end is None or s - prev_end >= QUEUED_PS):
            i = bisect.bisect_left(starts, s)
            t = min((starts[j] for j in (i - 1, i) if 0 <= j < len(starts)),
                    key=lambda t: abs(s - t))
            if abs(s - t) <= LAUNCH_REACH_PS:
                worst = min(worst, s - t)
        prev_end = e if prev_end is None else max(prev_end, e)
    return -worst


def _split_idle(busy, spans, w0, w1):
    """Idle ps of [w0, w1) outside the union of ``busy``, by innermost
    span, split at every span boundary."""
    cuts = sorted({w0, w1} | {t for s, e, _ in spans for t in (s, e)
                              if w0 < t < w1})
    labels = [_innermost(spans, a, b) for a, b in zip(cuts, cuts[1:])]
    merged = []
    for s, e in sorted(busy):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    idle, prev = {}, w0
    for s, e in merged + [[w1, w1]]:
        i = bisect.bisect_right(cuts, prev) - 1
        while prev < s:
            end = min(cuts[i + 1], s)
            idle[labels[i]] = idle.get(labels[i], 0) + (end - prev)
            prev, i = end, i + 1
        prev = max(prev, e)
    return idle


def reduce_scopes(path, layers=LAYERS) -> dict:
    """Reduce the xplane file at ``path`` (``.xplane.pb`` or gzipped);
    raises if it has no window span. Times in ns."""
    planes = list(_planes(_read(path)))
    spans, window = [], None
    for name, lines, emeta, _ in planes:
        if not name.startswith("/host:"):
            continue
        for _, events in lines:
            for mid, start, dur in events:
                ev_name = emeta.get(mid, ("",))[0]
                if ev_name == WINDOW_SPAN and window is None:
                    window = (start, start + dur)
                elif ev_name.startswith(SPAN_PREFIX):
                    spans.append((start, start + dur, ev_name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    devices = []
    for name, lines, emeta, snames in planes:
        if not name.startswith("/device:"):
            continue
        by_line = dict(lines)
        if "XLA Ops" not in by_line:
            continue
        mods = []
        programs = {}
        for mid, s, dur in by_line.get("XLA Modules", []):
            e = s + dur
            if min(e, w1) <= max(s, w0):
                continue
            mod = _FINGERPRINT.sub("", emeta.get(mid, ("",))[0])
            prog = programs.setdefault(mod, {"count": 0, "layers": {},
                                             "sublayers": {}})
            prog["count"] += 1
            mods.append((s, e, mod))
        mods.sort()
        starts = [m[0] for m in mods]
        tf_ops = {}
        for mid, (_, mstats) in emeta.items():
            for st in mstats:
                k, v = _stat(st, snames)
                if k == "tf_op":
                    tf_ops[mid] = v
        ops, busy = [], []
        for mid, s, dur in by_line["XLA Ops"]:
            e = s + dur
            if min(e, w1) <= max(s, w0):
                continue
            op = tf_ops.get(mid)
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else UNSCOPED
            ops.append((s, e, (mod, op, emeta.get(mid, ("",))[0])))
            busy.append((max(s, w0), min(e, w1)))
        tagged = untagged = 0
        for ps, (mod, op, event) in _self_times(ops, w0, w1):
            prog = programs.setdefault(mod, {"count": 0, "layers": {},
                                             "sublayers": {}})
            layer = op_layer(op, event, layers)
            if op:
                tagged += ps
                for sub, need in SUBLAYERS:
                    if path_names(op).issuperset(need):
                        prog["sublayers"][sub] = (
                            prog["sublayers"].get(sub, 0) + ps / 1e3)
            else:
                untagged += ps
            prog["layers"][layer] = prog["layers"].get(layer, 0) + ps / 1e3
        kinds = {mod: k for mod, prog in programs.items() for k in LAUNCH_SPANS
                 if any(lay.startswith(k + ".") for lay in prog["layers"])}
        off = _clock_offset(spans, mods, kinds)
        on_device = [(s - off, e - off, n) for s, e, n in spans]
        devices.append({
            "name": name,
            "programs": programs,
            "tagged_ns": tagged / 1e3,
            "untagged_ns": untagged / 1e3,
            "clock_offset_ns": off / 1e3,
            "idle": {k: v / 1e3 for k, v in
                     _split_idle(busy, on_device, w0, w1).items()},
        })
    return {
        "window_ns": (w1 - w0) / 1e3,
        "runs": sum(1 for s, _, n in spans if n == RUN_SPAN and w0 <= s < w1),
        "devices": devices,
    }


# ---------------------------------------------------------------------------
# For the metric readers
# ---------------------------------------------------------------------------

TRACE_ROOT = Path(__file__).resolve().parent / "out" / "trace"


def of(ctx: dict):
    """The reduction of the run's trace, kept in ``ctx["scopes"]`` for the
    next reader: the newest ``*.xplane.pb`` under ``bench/out/trace`` (the
    harness writes each traced run's profile there) whose window is the one
    ``ctx["trace"]`` read. None where there is none."""
    if "scopes" not in ctx:
        window = ctx["trace"]["window_ns"]
        ctx["scopes"] = None
        for path in sorted(TRACE_ROOT.rglob("*.xplane.pb"),
                           key=os.path.getmtime, reverse=True):
            try:
                red = reduce_scopes(path)
            except ValueError:
                continue
            if abs(red["window_ns"] - window) < 1e3:
                ctx["scopes"] = red
                break
    return ctx["scopes"]


def layer_ms(ctx: dict, role: str, layer: str, *, sublayer: bool = False):
    """Device self time of ``layer`` per execution of the program playing
    ``role`` ("train" or "sync"), in ms, averaged over chips. None where
    the trace has no such program, or the program none of the scopes that
    ``LAYERS`` gives the role (a program built without them)."""
    red = of(ctx)
    if red is None:
        return None
    name = ctx["modules"][role]
    per = []
    for d in red["devices"]:
        prog = d["programs"].get(name)
        if not prog or not prog["count"]:
            return None
        own = [k for k in prog["layers"] if k.startswith(role + ".")]
        if not own:
            return None
        got = prog["sublayers" if sublayer else "layers"].get(layer, 0.0)
        per.append(got / prog["count"])
    return sum(per) / len(per) / 1e6


def engine_idle_ms(ctx: dict):
    """Device idle ms per HFL period under the program's own host spans,
    the caller's callback (``hfl.on_step``) left out; averaged over chips.
    None where the trace has no ``hfl.run`` span."""
    red = of(ctx)
    if red is None or not red["runs"]:
        return None
    per = [sum(ns for k, ns in d["idle"].items()
               if k.startswith(SPAN_PREFIX) and k != "hfl.on_step")
           for d in red["devices"]]
    return sum(per) / len(per) / red["runs"] / 1e6
