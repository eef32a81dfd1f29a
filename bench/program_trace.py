"""The program's own spans and named scopes in a profiler trace, beside the
harness's.

:mod:`bench.tracing` keeps the harness's host spans (``bench.*``) and the
device ops, named by program and opcode.  This module adds what the
program writes into the same trace, on the same clock:

* its host spans (``jax.profiler.TraceAnnotation`` named ``repro.*``: one
  per call into a layer, with counts as span arguments), and
* each device op's scope path: the ``op_name`` that ``jax.named_scope``
  leaves in the op's metadata, e.g.
  ``jit(superstep)/while/body/closed_call/store_emit/scatter``.

Where the path comes from: the HLO module protos of the profile's metadata
plane (``/host:metadata``: one ``Hlo Proto`` stat per compiled program,
read here with a small protobuf walker), by the op's program and
instruction name.  The op events themselves carry none: on a TPU v5e chip
an ``XLA Ops`` event holds only its timing stats (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``), checked on a trace of
``iot1k.live``.

Two steps, as in :mod:`bench.tracing`:

* :func:`load` returns :func:`bench.tracing.load`'s structure with each
  device op event extended by its scope path (a fourth element) and the
  program's host spans added as lines of their own, each event
  ``[name, start_ns, duration_ns, {argument: value}]``;
* :func:`reduce` returns every key :func:`bench.tracing.reduce` returns,
  computed by it from the ``bench.*`` spans alone, and adds
  ``program_span_s`` and ``program_span_calls`` (segment-clipped seconds
  and calls by ``repro.*`` span name), ``program_span_args`` (the span
  arguments summed by span), ``scope_s`` (device seconds by innermost
  named scope, per chip) and ``idle_by_program_span_s`` (each idle gap
  charged to the innermost ``repro.*`` span over it).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import tracing

PREFIX = "repro."
NO_SPAN = "(no program span)"
NO_SCOPE = "(no scope)"
HLO_PROTO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
# name-stack frames JAX itself writes: control flow and calls; a frame
# with parentheses (``jit(f)``, ``vmap()``) is a transformation
JAX_FRAMES = frozenset({"while", "body", "cond", "closed_call", "core_call",
                        "checkpoint", "remat", "shard_map", "pjit",
                        "custom_jvp_call", "custom_vjp_call", "scan"})
_PLAIN = re.compile(r"^[A-Za-z_][A-Za-z0-9_.-]*$")


def innermost_scope(op_name: str) -> str:
    """The deepest ``jax.named_scope`` in an op's ``op_name``, or
    :data:`NO_SCOPE`.  The last frame is the primitive, not a scope; a
    ``pallas_call``'s frame above it is the kernel's ``name`` (the program
    names each of its kernels); and frames below a nested ``jit(...)``
    belong to a library function (``jnp.cumsum`` writes
    ``jit(cumsum)/...``), not to the program."""
    frames = op_name.split("/")
    frames = frames[:-2] if frames[-1] == "pallas_call" else frames[:-1]
    nested = [i for i, f in enumerate(frames) if f.startswith("jit(")][1:]
    for frame in reversed(frames[:nested[0]] if nested else frames):
        if _PLAIN.match(frame) and frame not in JAX_FRAMES:
            return frame
    return NO_SCOPE


# ---- the metadata plane's HLO protos, read with a protobuf walker ----------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one serialized message:
    an int for a varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield field, value


def _first(buf, field: int):
    for f, v in _fields(buf):
        if f == field:
            return v
    return None


def _text(v) -> str:
    return bytes(v).decode() if v is not None else ""


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    """``{instruction name: metadata.op_name}`` over every computation of
    one serialized ``HloProto``.  An instruction the compiler made without
    an ``op_name`` (a fusion it formed, a rewrite) takes the first one of
    the computations it calls.  HloProto.hlo_module = 1; module
    computations = 3; computation id = 5, instructions = 2; instruction
    name = 1, metadata = 7, called_computation_ids = 38 (packed or not);
    OpMetadata.op_name = 2."""
    module = _first(hlo_proto, 1)
    if module is None:
        return {}
    instrs, first = [], {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        cid, names = None, []
        for g, v in _fields(comp):
            if g == 5:
                cid = v
            if g != 2:
                continue
            name, op, calls = "", "", []
            for h, w in _fields(v):
                if h == 1:
                    name = _text(w)
                elif h == 7:
                    op = _text(_first(w, 2))
                elif h == 38 and isinstance(w, int):
                    calls.append(w)
                elif h == 38:
                    i = 0
                    while i < len(w):
                        c, i = _varint(w, i)
                        calls.append(c)
            instrs.append((name, op, calls))
            names.append(op)
        first[cid] = next((op for op in names if op), "")
    return {name: op or next((first.get(c, "") for c in calls
                              if first.get(c)), "")
            for name, op, calls in instrs}


def hlo_op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` from the HLO protos of a
    serialized XSpace's metadata plane.  Programs are keyed by the name
    the profile gives them (``jit_superstep(41)``) and by that name
    alone, for the one with the largest number in parentheses (on the CPU
    backend a program id: the newest).  XSpace.planes = 1; XPlane name = 2,
    event_metadata = 4, stat_metadata = 5 (map entries: key = 1, value =
    2); XEventMetadata name = 2, stats = 5; XStat metadata_id = 1,
    bytes_value = 6."""
    buf = memoryview(xplane)
    for f, plane in _fields(buf):
        if f != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        events, stat_ids = [], set()
        for g, entry in _fields(plane):
            if g == 4:
                events.append(_first(entry, 2))
            elif g == 5:
                meta = _first(entry, 2)
                if _text(_first(meta, 2)) == HLO_PROTO_STAT:
                    stat_ids.add(_first(meta, 1))
        out: Dict[str, Dict[str, str]] = {}
        newest: Dict[str, int] = {}
        for ev in events:
            name, names = "", {}
            for g, v in _fields(ev):
                if g == 2:
                    name = _text(v)
                elif g == 5 and _first(v, 1) in stat_ids:
                    names = _instruction_op_names(_first(v, 6) or b"")
            if names:
                out[name] = names
                base, _, pid = name.partition("(")
                pid = int(pid.rstrip(")")) if pid.rstrip(")").isdigit() \
                    else -1
                if pid >= newest.get(base, -2):
                    newest[base], out[base] = pid, names
        return out
    return {}


# ---- load -----------------------------------------------------------------

def _newest(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _instruction(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> dict:
    """:func:`bench.tracing.load` of the newest trace under ``trace_dir``,
    each device op extended by its scope path (``""`` when unknown), and
    the program's ``repro.*`` host spans with their arguments."""
    from jax.profiler import ProfileData
    path = _newest(trace_dir)
    trace = tracing.load(trace_dir)
    with open(path, "rb") as f:
        raw = f.read()
    hlo = hlo_op_names(raw)
    dev_lines = iter([ln for p in trace["planes"]
                      if tracing.is_device_plane(p["name"])
                      for ln in p["lines"] if ln["name"] == tracing.OPS_LINE])
    extra = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if tracing.is_device_plane(plane.name):
            by_name = {ln.name: ln for ln in plane.lines}
            ops = list(by_name[tracing.OPS_LINE].events) \
                if tracing.OPS_LINE in by_name else []
            if not ops:                # bench.tracing.load kept no line
                continue
            mods = sorted((e.start_ns, e.end_ns, e.name)
                          for e in (by_name[tracing.MODULES_LINE].events
                                    if tracing.MODULES_LINE in by_name
                                    else ()))
            starts = [m[0] for m in mods]
            loaded = next(dev_lines)
            for out, e in zip(loaded["events"], ops):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else ""
                names = hlo.get(mod) or hlo.get(mod.split("(")[0], {})
                out.append(names.get(_instruction(e.name), ""))
        else:
            lines = []
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns),
                        dict(e.stats)]
                       for e in line.events if e.name.startswith(PREFIX)]
                if evs:
                    lines.append({"name": line.name, "events": evs})
            if lines:
                extra.append({"name": plane.name, "lines": lines})
    trace["planes"] += extra
    return trace


# ---- reduce ---------------------------------------------------------------

def _split(trace: dict):
    """The trace without the program's spans (what :func:`bench.tracing.
    reduce` reads), and those spans."""
    base, program = [], []
    for plane in trace["planes"]:
        lines = []
        for ln in plane["lines"]:
            keep = [ev for ev in ln["events"]
                    if not ev[0].startswith(PREFIX)]
            program += [ev for ev in ln["events"]
                        if ev[0].startswith(PREFIX)]
            if keep:
                lines.append({"name": ln["name"], "events": keep})
        if lines:
            base.append({"name": plane["name"], "lines": lines})
    return {"planes": base}, program


def _innermost(spans: List[tuple], starts: List[float], longest: float,
               g0: float, g1: float, out: Dict[str, float]) -> None:
    """Charge ``[g0, g1]`` to the innermost span over each of its points:
    of the spans covering a point, the one that started last.  ``spans``
    are sorted by start (``starts``); none lasts over ``longest``."""
    lo = bisect.bisect_left(starts, g0 - longest)
    over = [sp for sp in spans[lo:bisect.bisect_left(starts, g1)]
            if sp[2] > g0]
    cuts = sorted({g0, g1} | {t for sp in over for t in sp[1:3]
                              if g0 < t < g1})
    for a, b in zip(cuts, cuts[1:]):
        cover = [sp for sp in over if sp[1] <= a and sp[2] >= b]
        name = max(cover, key=lambda sp: (sp[1], -sp[2]))[0] if cover \
            else NO_SPAN
        out[name] += b - a


def reduce(trace: dict) -> Optional[dict]:
    """:func:`bench.tracing.reduce` of the ``bench.*`` spans and device
    ops, plus the program's spans, arguments, scopes and the idle gaps by
    program span; ``None`` where :func:`bench.tracing.reduce` gives none.
    A span counts in ``program_span_calls`` and ``program_span_args`` if
    it starts inside the segment; its seconds are clipped to it."""
    base, program = _split(trace)
    red = tracing.reduce(base)
    if red is None:
        return None
    seg = next(ev for p in base["planes"] if not tracing.is_device_plane(
        p["name"]) for ln in p["lines"] for ev in ln["events"]
        if ev[0] == tracing.SEGMENT)
    lo, hi = seg[1], seg[1] + seg[2]
    span_ns, calls = collections.Counter(), collections.Counter()
    args: Dict[str, Dict[str, float]] = {}
    spans = []
    for ev in program:
        s, e = ev[1], ev[1] + ev[2]
        if s < hi and e > lo:
            span_ns[ev[0]] += min(e, hi) - max(s, lo)
            spans.append((ev[0], s, e))
        if lo <= s < hi:
            calls[ev[0]] += 1
            a = args.setdefault(ev[0], {})
            for k, v in (ev[3] if len(ev) > 3 else {}).items():
                if isinstance(v, (int, float)):
                    a[k] = a.get(k, 0) + v
    spans.sort(key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((sp[2] - sp[1] for sp in spans), default=0.0)
    scope_ns = collections.Counter()
    idle_ns: Dict[str, float] = collections.Counter()
    devices = [[ev for ln in p["lines"] if ln["name"] == tracing.OPS_LINE
                for ev in ln["events"]]
               for p in trace["planes"] if tracing.is_device_plane(p["name"])]
    devices = [d for d in devices if d]
    for evs in devices:
        evs = sorted((ev for ev in evs if ev[1] < hi and ev[1] + ev[2] > lo),
                     key=lambda ev: ev[1])
        for i, ev in enumerate(evs):   # as bench.tracing: an op enclosing
            if i + 1 < len(evs) and evs[i + 1][1] < ev[1] + ev[2]:
                continue               # the next counts only as busy time
            s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
            path = ev[3] if len(ev) > 3 else ""
            scope_ns[innermost_scope(path) if path else NO_SCOPE] += e - s
        prev = lo
        for s, e in tracing._union([(max(ev[1], lo), min(ev[1] + ev[2], hi))
                                    for ev in evs]) + [(hi, hi)]:
            if s > prev:
                _innermost(spans, starts, longest, prev, s, idle_ns)
            prev = max(prev, e)
    n = red["n_chips"]
    red.update(
        program_span_s={k: v * 1e-9 for k, v in span_ns.items()},
        program_span_calls=dict(calls),
        program_span_args=args,
        scope_s={k: v / n * 1e-9 for k, v in scope_ns.items()},
        idle_by_program_span_s={k: v / n * 1e-9 for k, v in idle_ns.items()})
    return red


def breakdown(red: dict) -> dict:
    """:func:`bench.tracing.breakdown` plus ``idle_gaps_program``: the ten
    largest idle shares by ``repro.*`` span."""
    out = tracing.breakdown(red)
    gaps = sorted(red["idle_by_program_span_s"].items(),
                  key=lambda kv: -kv[1])[:10]
    out["idle_gaps_program"] = [[k, v] for k, v in gaps]
    return out
