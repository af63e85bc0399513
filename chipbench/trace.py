"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is a list of planes; a plane has lines; a line has events with a
name, a start and a duration in nanoseconds, and a few descriptive stats.
``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes;
``reduce`` works on that plain form, so a test can hand it a synthetic
trace.

What ``reduce`` gives, inside the window (the host span named
``WINDOW`` that the harness puts around the measured work):
  * ``busy_s``: the union of the intervals in which an operation ran on a
    device, averaged over the devices;
  * ``ops``: seconds and count per device operation, keyed by the HLO
    instruction's name and the op path the compiler recorded for it
    (``op_key``), averaged over the devices; control-flow ops (a ``while``
    spans the ops of its body) count in ``busy_s`` but not here;
  * ``gaps``: the longest idle gaps on the first device, each labelled by
    the host activity that overlaps it most.
Kernel families are attributed by the per-layer metric readers, each with
its own list of name patterns (``attributed``); what no reader claims is
reported in the breakdown as it is.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Event", "Line", "Plane", "Summary", "WINDOW", "load", "reduce",
           "attributed", "op_key"]

WINDOW = "chipbench_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OP_LINE = "XLA Ops"
# stats that carry the op path (name scopes, jit names) of a device op
_PATH_STATS = ("tf_op", "hlo_op")


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    dur: float              # ns
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    ops: dict               # op_key -> [seconds, count]
    gaps: list              # [[label, seconds], ...] longest first

    def seconds_matching(self, patterns) -> float:
        return sum(v[0] for k, v in self.ops.items()
                   if attributed(k, patterns))


def attributed(key: str, patterns) -> bool:
    return any(p in key for p in patterns)


def op_key(ev: Event) -> str:
    """The HLO instruction's own name (a TPU trace names a device op by its
    whole HLO text, ``%name = type op(operands)``: operands must not count,
    or a fusion that reads a kernel's output would be taken for the
    kernel), plus the op path the compiler recorded, where it did."""
    name = ev.name.split(" = ", 1)[0].lstrip("%")
    path = ev.stats.get("tf_op") or ""
    return f"{name} | {path}" if path else name


def _container(key: str) -> bool:
    """Control-flow ops whose events span the ops of their bodies."""
    return key.split(".", 1)[0] in ("while", "conditional", "call")


def _stat_value(v):
    for attr in ("str_value", "int64_value", "uint64_value", "double_value"):
        if hasattr(v, attr):
            return getattr(v, attr)
    return v


def load(trace_dir: str) -> list:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    planes = []
    for p in pd.planes:
        keep_stats = bool(_DEVICE.match(p.name))
        lines = []
        for ln in p.lines:
            evs = []
            for ev in ln.events:
                stats = {}
                if keep_stats:
                    for item in ev.stats:
                        k, v = item if isinstance(item, tuple) else (
                            getattr(item, "name", None), item)
                        if k in _PATH_STATS:
                            stats[k] = str(_stat_value(v))
                evs.append(Event(ev.name, float(ev.start_ns),
                                 float(ev.duration_ns), stats))
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, lines))
    return planes


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _window(planes) -> tuple:
    for p in planes:
        if _DEVICE.match(p.name):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name == WINDOW:
                    return ev.start, ev.end
    raise ValueError(f"no host span named {WINDOW!r} in the trace")


def _host_label(planes, t0, t1, window) -> str:
    """The host event overlapping [t0, t1) most, the window span and any
    event as long as the window left out; ties go to the shorter one."""
    best, best_key = "no host activity", (0.0, 0.0)
    wlen = window[1] - window[0]
    for p in planes:
        if _DEVICE.match(p.name):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name == WINDOW or ev.dur >= wlen:
                    continue
                ov = min(ev.end, t1) - max(ev.start, t0)
                if ov <= 0:
                    continue
                key = (ov, -ev.dur)
                if key > best_key:
                    best, best_key = ev.name, key
    return best


def reduce(planes, top: int = 10) -> Summary:
    t0, t1 = _window(planes)
    devices = [p for p in planes if _DEVICE.match(p.name)]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy_total, ops, first_busy = 0.0, {}, None
    for p in devices:
        ivs = []
        for ln in p.lines:
            if ln.name != _OP_LINE:
                continue
            for ev in ln.events:
                s, e = max(ev.start, t0), min(ev.end, t1)
                if e <= s:
                    continue
                ivs.append((s, e))
                key = op_key(ev)
                if _container(key):
                    continue
                rec = ops.setdefault(key, [0.0, 0])
                rec[0] += (e - s) * 1e-9
                rec[1] += 1
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged)
        if first_busy is None:
            first_busy = merged
    n = len(devices)
    ops = {k: [v[0] / n, v[1] / n] for k, v in ops.items()}
    edges = [t0] + [x for iv in first_busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [[_host_label(planes, s, e, (t0, t1)), (e - s) * 1e-9]
                for s, e in gaps[:top]]
    return Summary(window_s=(t1 - t0) * 1e-9, busy_s=busy_total / n * 1e-9,
                   devices=n, ops=ops, gaps=labelled)
