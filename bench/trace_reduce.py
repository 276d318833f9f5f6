"""From a profiler trace to device busy time, idle share and breakdowns.

The JAX profiler writes an XSpace (``*.xplane.pb``).  On a TPU each chip
is a plane ``/device:TPU:<i>`` whose line ``XLA Modules`` holds one event
per program run and ``XLA Ops`` one per operation (a ``while`` op encloses
the operations of its body).  Copies from the host run as DMA and are not
among these events.  The host's plane ``/host:CPU`` has a line for the
interpreter's main thread (``python``, ``python3``, ...) with the
profiler's Python frames and the benchmark's own annotations.
All events share one clock, in nanoseconds from the trace's start.

* busy: the union of the device's program and operation intervals inside
  the window, averaged over the chips that ran anything;
* idle share: 1 - busy / window;
* compute: the union of the operations alone (what ``pr_iter_roofline``
  divides by);
* breakdown: the operations with most self time (time not inside an
  enclosed operation), and the device's idle gaps, each named by the
  innermost Python frame that spans most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Optional

import numpy as np

WINDOW_SPAN = "bench_window"
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass
class Events:
    """Intervals in ns: per chip, ``(start, end, name, line)``; on the
    host's Python line, ``(start, end, name)``."""

    device: dict
    host: list


def start_trace(log_dir: str) -> None:
    import jax
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


def window_annotation():
    """The span the harness puts around its measured window."""
    import jax
    return jax.profiler.TraceAnnotation(WINDOW_SPAN)


def find_xspace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_xspace(path: str) -> Events:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            evs = []
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    evs.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, line.name) for e in line.events)
            if evs:
                device[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                # the main thread, named after the interpreter
                if line.name.startswith("python"):
                    host.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
    return Events(device=device, host=host)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def window_bounds(ev: Events, span: str = WINDOW_SPAN) -> tuple[float, float]:
    """The host span named ``span`` (the harness's window), or else the
    device events' extent."""
    spans = [(s, e) for s, e, name in ev.host if name == span]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    allev = [x for evs in ev.device.values() for x in evs]
    return min(x[0] for x in allev), max(x[1] for x in allev)


def self_times(ops) -> dict:
    """Self time of each operation by short name (``module:op``): its
    duration less that of the operations it encloses."""
    out = {}
    stack = []   # (end, key)
    for s, e, key in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] = out.get(stack[-1][1], 0.0) - (e - s)
        out[key] = out.get(key, 0.0) + (e - s)
        stack.append((e, key))
    return out


def _short_op(name: str) -> str:
    m = re.match(r"%?([\w.\-]+)", name)
    return m.group(1) if m else name[:40]


def _short_module(name: str) -> str:
    return name.split("(")[0]


def name_gap(host, s: float, e: float) -> str:
    """The innermost host frame covering at least half of the gap
    ``[s, e]``, or else the one that covers most of it."""
    best, best_len, most, most_cov = None, np.inf, None, 0.0
    for hs, he, name in host:
        cov = min(he, e) - max(hs, s)
        if cov <= 0:
            continue
        if cov >= 0.5 * (e - s) and he - hs < best_len:
            best, best_len = name, he - hs
        if cov > most_cov:
            most, most_cov = name, cov
    return best or most or "(no host frame)"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    compute_s: float
    chips: int
    device_ops: list     # [[name, seconds]], most self time first
    idle_gaps: list      # [[name, seconds]], most idle time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def summarize(ev: Events, top: int = 10,
              span: str = WINDOW_SPAN) -> TraceSummary:
    lo, hi = window_bounds(ev, span)
    busy, compute = [], []
    ops_self = {}
    gaps = {}
    for evs in ev.device.values():
        merged = union([(s, e) for s, e, _, _ in evs], lo, hi)
        busy.append(_length(merged))
        compute.append(_length(union(
            [(s, e) for s, e, _, line in evs if line == OPS], lo, hi)))
        modules = sorted((s, e, n) for s, e, n, line in evs
                         if line == MODULES)
        starts = [m[0] for m in modules]
        ops = []
        for s, e, n, line in evs:
            if line != OPS or e <= lo or s >= hi:
                continue
            i = np.searchsorted(starts, s, side="right") - 1
            mod = _short_module(modules[i][2]) if i >= 0 else "?"
            ops.append((s, e, f"{mod}:{_short_op(n)}"))
        for k, v in self_times(ops).items():
            ops_self[k] = ops_self.get(k, 0.0) + v
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                key = name_gap(ev.host, gs, ge)
                gaps[key] = gaps.get(key, 0.0) + (ge - gs)
    chips = max(len(ev.device), 1)
    rank = lambda d: [[k, v / 1e9 / chips] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return TraceSummary(window_s=(hi - lo) / 1e9,
                        busy_s=float(np.mean(busy)) / 1e9 if busy else 0.0,
                        compute_s=float(np.mean(compute)) / 1e9
                        if compute else 0.0,
                        chips=len(ev.device), device_ops=rank(ops_self),
                        idle_gaps=rank(gaps))


def reduce_trace(log_dir: str) -> TraceSummary:
    return summarize(read_xspace(find_xspace(log_dir)))


def idle_percent(trace: Optional[TraceSummary]) -> Optional[float]:
    """The device's idle share of the traced window, in %, or None where
    nothing was traced or nothing ran on the device."""
    if trace is None or trace.busy_s <= 0:
        return None
    return 100.0 * trace.idle_share
