"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The benchmark wraps its measured window in a host span named
:data:`WINDOW_SPAN` and each call into the program in a span of its own
(``bench.job``, ``bench.lookup``, ...); the program writes its own host
spans, ``elsar.<name>``.  From the trace it takes:

* the device planes (``/device:TPU:<n>``, in the order of ``n``) and,
  on each, the events of its ``XLA Ops`` line (every event of the plane
  where that line is missing), clipped to the window: ``device_ops``;
* **busy** time: the union of those events' intervals inside the
  window, averaged over the devices; idle share is ``1 - busy/window``;
* **module time**: the summed durations of the ``XLA Modules`` events
  whose name matches a pattern (the jitted program's name, e.g.
  ``_flat_impl`` or ``rmi_bucket``);
* per device, the time in which ops whose name matches a pattern run
  (:meth:`Reduced.op_seconds`), and the part of it in which no other op
  runs on that device (:meth:`Reduced.exposed_seconds`): a collective's
  time not hidden behind compute;
* the **breakdown**: the device ops that took most time, and the longest
  idle gaps on the first device.  A gap is labelled by the innermost
  benchmark span around its midpoint, then ``/`` and the shortest
  ``elsar.`` span open at that instant on any host thread, without its
  prefix (``bench.job/sort.gather``); a gap inside no ``elsar.`` span
  keeps the bare benchmark label.

Timestamps of host and device events in one trace share one clock
(nanoseconds from the start of the profile).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PROGRAM_SPAN_PREFIX = "elsar."
NO_SPAN = "no benchmark span"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def _union(intervals: "list[tuple[float, float]]") -> "list[list[float]]":
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _covered(intervals) -> float:
    return sum(b - a for a, b in _union(intervals))


@dataclasses.dataclass
class Reduced:
    """What one trace says about its window."""

    window_s: float
    busy_s: float  # union of device-op intervals, mean over devices
    n_devices: int
    module_ns: "dict[str, float]"  # XLA module name -> summed ns
    op_ns: "dict[str, float]"  # device op name (as in the HLO) -> summed ns
    gaps: "list[tuple[str, float]]"  # (host span label, seconds)
    # per device plane: (op name, start ns, end ns), clipped to the window
    device_ops: "list[list[tuple[str, float, float]]]"

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> "float | None":
        """Device seconds of the modules whose name matches ``pattern``
        (``None`` when no such module ran)."""
        rx = re.compile(pattern)
        hits = [ns for name, ns in self.module_ns.items() if rx.search(name)]
        return sum(hits) / 1e9 if hits else None

    def op_seconds(self, pattern: str) -> "list[float]":
        """Per device, the seconds in which an op whose name matches
        ``pattern`` runs (the union of their intervals)."""
        rx = re.compile(pattern)
        return [_covered([(a, b) for name, a, b in ops if rx.search(name)])
                / 1e9 for ops in self.device_ops]

    def exposed_seconds(self, pattern: str) -> "list[float]":
        """Per device, the seconds in which an op whose name matches
        ``pattern`` runs and no other op does."""
        rx = re.compile(pattern)
        out = []
        for ops in self.device_ops:
            other = [(a, b) for name, a, b in ops if not rx.search(name)]
            out.append((_covered([(a, b) for _, a, b in ops])
                        - _covered(other)) / 1e9)
        return out

    def breakdown(self) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:TOP]
        return {
            "device_ops": [[name, ns / 1e9] for name, ns in ops],
            "idle_gaps": [[label, s] for label, s in self.gaps[:TOP]],
        }


def find_xplane(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                  recursive=True)
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _module_name(raw: str) -> str:
    # "jit__flat_impl(1234)" and "jit__flat_impl" are one module
    return re.sub(r"\(\d+\)$", "", raw)


def _op_name(raw: str) -> str:
    # a TPU op event is named by its whole HLO line,
    # "%sort.21 = (s32[1441792]{0}, ...) sort(...), ...": keep "sort.21"
    return raw.split(" = ", 1)[0].lstrip("%")


def reduce(path: str) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def _innermost(spans, points) -> "list[str | None]":
    """For each of ``points``, the name of the shortest of ``spans``
    (``(name, start, end)``) open at it, ties to the first name in sort
    order; ``None`` where none is open."""
    spans = sorted(spans, key=lambda s: (s[2] - s[1], s[0]))
    start = np.array([a for _, a, _ in spans], dtype=float)
    end = np.array([b for _, _, b in spans], dtype=float)
    out = []
    for t in points:
        hit = np.flatnonzero((start <= t) & (t <= end))
        out.append(spans[hit[0]][0] if hit.size else None)
    return out


def reduce_profile(profile) -> Reduced:
    spans: list[tuple[str, float, float]] = []
    program: list[tuple[str, float, float]] = []
    devices: list = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, *iv))
                elif ev.name.startswith(PROGRAM_SPAN_PREFIX):
                    program.append((ev.name[len(PROGRAM_SPAN_PREFIX):], *iv))
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0]
    if not devices:
        raise ValueError("trace holds no TPU device plane")
    devices.sort(key=lambda d: d[0])

    module_ns: dict[str, float] = {}
    op_ns: dict[str, float] = {}
    device_ops = []
    for _, plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = ([lines[OPS_LINE]] if OPS_LINE in lines
                    else list(lines.values()))
        ops = []
        for line in op_lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= w0 or a >= w1:
                    continue
                a, b = max(a, w0), min(b, w1)
                name = _op_name(ev.name)
                ops.append((name, a, b))
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        device_ops.append(ops)
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= w0 or a >= w1:
                    continue
                name = _module_name(ev.name)
                module_ns[name] = module_ns.get(name, 0.0) + (
                    min(b, w1) - max(a, w0)
                )
    unions = [_union([(a, b) for _, a, b in ops]) for ops in device_ops]

    edges = [w0] + [x for iv in unions[0] for x in iv] + [w1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    mids = [(a + b) / 2 for a, b in idle]
    inner = [(n, a, b) for n, a, b in spans if n != WINDOW_SPAN]
    gaps = []
    for (a, b), bench, phase in zip(idle, _innermost(inner, mids),
                                    _innermost(program, mids)):
        label = bench or NO_SPAN
        if phase is not None:
            label = f"{label}/{phase}"
        gaps.append((label, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(sum(b - a for a, b in u) for u in unions)
        / len(unions) / 1e9,
        n_devices=len(devices),
        module_ns=module_ns,
        op_ns=op_ns,
        gaps=gaps,
        device_ops=device_ops,
    )
