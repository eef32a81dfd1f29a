"""From a profiler trace to the numbers the readers take.

Two steps, so the reduction can be checked on a small recorded trace:

* :func:`load` reads the ``.xplane.pb`` the JAX profiler wrote into a
  plain structure — ``{"planes": [{"name", "lines": [{"name", "events":
  [[name, start_ns, duration_ns], ...]}]}]}`` — keeping each device's op
  line (ops named by program and opcode, :func:`op_name`) and the
  harness's host spans (``bench.*``);
* :func:`reduce` finds the traced segment (the host span
  ``bench.segment``), the device busy time (the union of op intervals on
  each chip's op line, clipped to the segment), the time by op (an op
  that encloses another on its line counts only as busy time, not as an
  op), and the idle gaps, each charged to the host span it overlaps.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SEGMENT = "bench.segment"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(module: str, hlo: str) -> str:
    """A short, matchable name for one device op event: ``<module>/
    <opcode>[:<custom-call target>]/<instruction>``, e.g.
    ``jit_superstep/custom-call:tpu_custom_call/closed_call.15``.  The
    trace names an op by its whole HLO text; the module is the XLA
    program the op ran in (its name without the fingerprint)."""
    instr = hlo.split(" = ", 1)[0].lstrip("%")
    rest = hlo.split(" = ", 1)[-1]
    m = _OPCODE.search(rest)
    code = m.group(1) if m else "op"
    t = _TARGET.search(rest)
    if t:
        code += ":" + t.group(1)
    return f"{module.split('(')[0]}/{code}/{instr}"


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a plain dict:
    each device's ops (named by :func:`op_name`) and the host spans."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = []
        if is_device_plane(plane.name):
            by_name = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in by_name:
                continue
            mods = sorted((e.start_ns, e.end_ns, e.name)
                          for e in (by_name[MODULES_LINE].events
                                    if MODULES_LINE in by_name else ()))
            starts = [m[0] for m in mods]
            evs = []
            for e in by_name[OPS_LINE].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                    else "(no module)"
                evs.append([op_name(mod, e.name), float(e.start_ns),
                            float(e.duration_ns)])
            if evs:
                lines.append({"name": OPS_LINE, "events": evs})
        else:
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIX)]
                if evs:
                    lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    return max(s, lo), min(e, hi)


def reduce(trace: dict) -> Optional[dict]:
    """Segment length, per-chip busy time, time by op and idle gaps by
    host span; ``None`` when the trace holds no segment or no device op."""
    host, devices = [], []
    for plane in trace["planes"]:
        if is_device_plane(plane["name"]):
            devices.append([ev for ln in plane["lines"]
                            if ln["name"] == OPS_LINE for ev in ln["events"]])
        else:
            host += [ev for ln in plane["lines"] for ev in ln["events"]]
    seg = [ev for ev in host if ev[0] == SEGMENT]
    devices = [d for d in devices if d]
    if not seg or not devices:
        return None
    lo, hi = seg[0][1], seg[0][1] + seg[0][2]
    window_ns = hi - lo
    busy_ns, op_ns, calls = [], collections.Counter(), collections.Counter()
    gaps_by_span: Dict[str, float] = collections.Counter()
    spans = sorted(((ev[0][len(SPAN_PREFIX):], ev[1], ev[1] + ev[2])
                    for ev in host if ev[0] != SEGMENT),
                   key=lambda sp: sp[1])
    for evs in devices:
        evs = sorted((ev for ev in evs
                      if ev[1] < hi and ev[1] + ev[2] > lo),
                     key=lambda ev: ev[1])
        ivs = [_clip(ev[1], ev[1] + ev[2], lo, hi) for ev in evs]
        merged = _union(ivs)
        busy_ns.append(sum(e - s for s, e in merged))
        for i, ev in enumerate(evs):
            end = ev[1] + ev[2]
            if i + 1 < len(evs) and evs[i + 1][1] < end:
                continue                      # encloses the next op
            s, e = ivs[i]
            op_ns[ev[0]] += e - s
            calls[ev[0]] += 1
        gaps, prev = [], lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        j = 0                                 # spans and gaps both sorted
        for g0, g1 in gaps:
            while j < len(spans) and spans[j][2] <= g0:
                j += 1
            hit, i = 0.0, j
            while i < len(spans) and spans[i][1] < g1:
                o = min(spans[i][2], g1) - max(spans[i][1], g0)
                if o > 0:
                    gaps_by_span[spans[i][0]] += o
                    hit += o
                i += 1
            if g1 - g0 - hit > 0:
                gaps_by_span["(no span)"] += g1 - g0 - hit
    n = len(devices)
    return {
        "n_chips": n,
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / n * 1e-9,
        "op_s": {k: v / n * 1e-9 for k, v in op_ns.items()},
        "op_calls": {k: v / n for k, v in calls.items()},
        "idle_by_span_s": {k: v / n * 1e-9 for k, v in gaps_by_span.items()},
    }


def matching(op_s: Dict[str, float], patterns) -> Dict[str, float]:
    """The ops matching any of ``patterns``; a pattern is a string the
    op's name contains, or a list of strings it contains all of."""
    pats = [[p] if isinstance(p, str) else p for p in patterns]
    return {k: v for k, v in op_s.items()
            if any(all(x in k for x in p) for p in pats)}


def breakdown(red: dict) -> dict:
    top = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(red["idle_by_span_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
