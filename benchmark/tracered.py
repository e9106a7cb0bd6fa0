"""Reduction from a profiler trace to busy time, program times and gaps.

The profiler's `.xplane.pb` is read with JAX alone into a small plain
form, `{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, dur_ns], ...]}]}]}`, and every number is computed from that
form, so the arithmetic is checked on the recorded excerpt under
`testdata/` (selftest.py) and is the same in every later PR.

On a TPU the planes named `/device:TPU:<n>` are the chips. Their line
`XLA Modules` has one event per launched program, named
`jit_<function>(<fingerprint>)`; `XLA Ops` has the operations inside
them (kept by their short name, `fusion.33`, not the whole HLO line). Busy time is the union of the `XLA Ops` intervals (of the module
intervals where a trace has no such line), averaged over the chips that
ran anything. Host planes are kept only for the `bench/...` spans this
benchmark writes with `jax.profiler.TraceAnnotation`.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
MODULES, OPS = "XLA Modules", "XLA Ops"
HOST_SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"    # the traced stretch itself, not a layer


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            short = device and line.name != MODULES
            evs = [[op_name(e.name) if short else e.name,
                    int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(HOST_SPAN_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# --------------------------------------------------------------- reduction

def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def _union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _busy_intervals(plane: dict) -> list:
    evs = _line(plane, OPS) or _line(plane, MODULES)
    return _union([(s, s + d) for _n, s, d in evs if d > 0])


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips used;
    0.0 when no chip ran anything."""
    per = [sum(e - s for s, e in _busy_intervals(p))
           for p in device_planes(trace)]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) / 1e9 if per else 0.0


def program_name(event_name: str) -> str:
    """`jit_hint_hash_match(123...)` -> `jit_hint_hash_match`."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def programs(trace: dict) -> dict:
    """{program: [launches, seconds]} over every chip's `XLA Modules`."""
    out: dict = {}
    for p in device_planes(trace):
        for n, _s, d in _line(p, MODULES):
            c = out.setdefault(program_name(n), [0, 0.0])
            c[0] += 1
            c[1] += d / 1e9
    return out


def op_name(event_name: str) -> str:
    """`%fusion.33 = u8[6144]{...} fusion(...)` -> `fusion.33`."""
    return event_name.split(" = ")[0].lstrip("%")


def top_device_ops(trace: dict, k: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time;
    programs where a trace has no operation line."""
    tot: dict = {}
    for p in device_planes(trace):
        evs = _line(p, OPS)
        for n, _s, d in (evs or _line(p, MODULES)):
            key = op_name(n) if evs else program_name(n)
            tot[key] = tot.get(key, 0.0) + d / 1e9
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def host_spans(trace: dict) -> list:
    """Sorted [(start, end, name)] of the benchmark's host spans."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            out += [(s, s + d, n) for n, s, d in ln["events"]
                    if n.startswith(HOST_SPAN_PREFIX)]
    return sorted(out)


def idle_gaps(trace: dict, t0_ns: int, t1_ns: int, k: int = 10) -> list:
    """[[what the host was doing, seconds]]: the first chip's idle time
    inside [t0, t1), each gap split over the host spans that overlap it
    (innermost span wins where spans nest) and the rest booked to
    `no_host_span`; the k largest totals."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = _busy_intervals(planes[0])
    gaps, cur = [], t0_ns
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, t1_ns)))
        cur = max(cur, e)
        if cur >= t1_ns:
            break
    if cur < t1_ns:
        gaps.append((cur, t1_ns))
    spans = [x for x in host_spans(trace) if x[2] != WINDOW_SPAN]
    tot: dict = {}
    starts = [s for s, _e, _n in spans]
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        lo = bisect.bisect_left(starts, g0 - 500_000_000)
        hi = bisect.bisect_right(starts, g1)
        near = [x for x in spans[lo:hi] if x[1] > g0]
        # innermost first: a shorter span takes its part of the gap
        # before the longer one that holds it
        near.sort(key=lambda x: x[1] - x[0])
        covered: list = []
        for s, e, n in near:
            s, e = max(s, g0), min(e, g1)
            part = e - s - sum(min(e, ce) - max(s, cs)
                               for cs, ce in covered
                               if min(e, ce) > max(s, cs))
            if part > 0:
                tot[n] = tot.get(n, 0) + part
                covered = _union(covered + [(s, e)])
        rest = (g1 - g0) - sum(e - s for s, e in covered)
        if rest > 0:
            tot["no_host_span"] = tot.get("no_host_span", 0) + rest
    return [[n, v / 1e9] for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
