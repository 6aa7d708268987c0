"""Reduction of a profiler trace to device busy time, program and kernel
time, and device idle time split by what the host was doing.

A trace is read into plain data (``load_xplane``): a list of planes, each
``{"name": str, "lines": {line name: [[name, start_ns, dur_ns, stats], ...]}}``.
``digest`` keeps only what ``reduce`` reads, so a small recorded trace can be
kept as JSON beside the tests.

Device planes are ``/device:TPU:<n>``: their ``XLA Ops`` line gives the
busy intervals and the kernels, their ``XLA Modules`` line one event a
program run, named after the jitted function (``jit_<name>(<id>)``).  An op
event's name is its HLO instruction, either bare (``block_stats.1``) or as
the TPU profiler writes it, the whole instruction
(``%block_stats.1 = (s32[...]) custom-call(...), ...``).  Host
spans are the harness's ``TraceAnnotation``s; the one named ``window``
bounds the measured window on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import re

__all__ = ["SPAN_LABELS", "Summary", "load_xplane", "digest", "op_name",
           "reduce"]

SPAN_LABELS = ("estimate", "stage", "app", "fetch", "plan", "account")
WINDOW = "window"
OPS, MODULES = "XLA Ops", "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_INSTR = re.compile(r"^\s*%?([^\s=%]+)\s*(?:=|$)")
_NS = 1e-9


def load_xplane(path) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                [e.name, float(e.start_ns), float(e.duration_ns),
                 {k: str(v) for k, v in e.stats}] for e in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def digest(planes: list) -> list:
    """Only the planes, lines and events ``reduce`` reads."""
    out = []
    for p in planes:
        if _DEVICE.match(p["name"]):
            lines = {k: v for k, v in p["lines"].items() if k in (OPS, MODULES)}
        else:
            keep = set(SPAN_LABELS) | {WINDOW}
            lines = {k: [e for e in v if e[0] in keep]
                     for k, v in p["lines"].items()}
            lines = {k: v for k, v in lines.items() if v}
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over the device planes
    n_devices: int
    programs: dict                # program name -> [runs, device seconds]
    ops: dict                     # op key -> [runs, device seconds, name]
    idle_by_span: dict            # host span label -> idle device seconds

    def program(self, name: str) -> tuple:
        """(runs, seconds) of the program of jitted function ``name``."""
        runs, secs = self.programs.get(f"jit_{name}", (0, 0.0))
        return runs, secs

    def kernel(self, name: str) -> tuple:
        """(runs, seconds) of the device ops named ``name`` or ``name.<n>``
        (a Pallas kernel's HLO instruction takes the kernel's name)."""
        hits = [v for v in self.ops.values()
                if v[2] == name or v[2].startswith(name + ".")]
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v[1]] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_name(event_name: str) -> str:
    """The HLO instruction's name: ``block_stats.1`` of ``%block_stats.1 =
    ...`` or of ``block_stats.1``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def _op_key(event) -> str:
    name, _, _, stats = event
    module = stats.get("hlo_module") or stats.get("module_name")
    return f"{module}/{name}" if module else name


def reduce(planes: list) -> Summary | None:
    """None where the trace holds no device plane (a CPU run).  Raises where
    the host's ``window`` span holds no device op, rather than measure over
    another window."""
    devices = [p for p in planes if _DEVICE.match(p["name"])]
    if not devices:
        return None
    host = [e for p in planes if not _DEVICE.match(p["name"])
            for evs in p["lines"].values() for e in evs]
    ops = [e for p in devices for e in p["lines"].get(OPS, [])]
    if not ops:
        return None
    windows = [e for e in host if e[0] == WINDOW]
    lo = min(e[1] for e in windows) if windows else 0.0
    hi = max(e[1] + e[2] for e in windows) if windows else 0.0
    if not any(lo <= e[1] < hi for e in ops):
        raise ValueError("the trace has no host 'window' span that holds a "
                         "device op: device and host clocks do not line up")
    spans = sorted((e[1], e[1] + e[2], e[0]) for e in host
                   if e[0] in SPAN_LABELS)

    programs, ops, idle = {}, {}, {}
    busy = 0.0
    for n, p in enumerate(devices):
        evs = _clip_events(p["lines"].get(OPS, []), lo, hi)
        merged = _merge([[s, s + d] for _, s, d, _ in evs])
        busy += sum(e - s for s, e in merged)
        for e in evs:
            acc = ops.setdefault(_op_key(e), [0, 0.0, op_name(e[0])])
            acc[0] += 1
            acc[1] += e[2] * _NS
        for name, s, d, _ in _clip_events(p["lines"].get(MODULES, []), lo, hi):
            acc = programs.setdefault(name.split("(")[0], [0, 0.0])
            acc[0] += 1
            acc[1] += d * _NS
        if n == 0:
            gaps = _gaps(merged, lo, hi)
            idle = _attribute(gaps, spans)
    return Summary(window_s=(hi - lo) * _NS, busy_s=busy * _NS / len(devices),
                   n_devices=len(devices), programs=programs, ops=ops,
                   idle_by_span=idle)


def _clip_events(events, lo: float, hi: float) -> list:
    """Events that start inside [lo, hi), cut to end by ``hi``."""
    return [[n, s, min(s + d, hi) - s, st] for n, s, d, st in events
            if lo <= s < hi]


def _gaps(merged: list, lo: float, hi: float) -> list:
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _attribute(gaps: list, spans: list) -> dict:
    """Idle seconds of each host span label; what no span covers is 'none'."""
    out = {}
    j = 0
    for g0, g1 in gaps:
        while j < len(spans) and spans[j][1] <= g0:
            j += 1
        covered = 0.0
        k = j
        while k < len(spans) and spans[k][0] < g1:
            s, e, label = spans[k]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                out[label] = out.get(label, 0.0) + overlap * _NS
                covered += overlap
            k += 1
        if g1 - g0 - covered > 0:
            out["none"] = out.get("none", 0.0) + (g1 - g0 - covered) * _NS
    return out
