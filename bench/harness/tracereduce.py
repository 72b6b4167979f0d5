"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The benchmark wraps its measured window in a host span named
:data:`WINDOW_SPAN` and each call into the program in a span of its own
(``bench.job``, ``bench.lookup``, ...).  From the trace it takes:

* the device planes (``/device:TPU:<n>``) and, on each, the events of
  its ``XLA Ops`` line (every event of the plane where that line is
  missing);
* **busy** time: the union of those events' intervals inside the
  window, averaged over the devices; idle share is ``1 - busy/window``;
* **module time**: the summed durations of the ``XLA Modules`` events
  whose name matches a pattern (the jitted program's name, e.g.
  ``_flat_impl`` or ``rmi_bucket``);
* the **breakdown**: the device ops that took most time, and the longest
  idle gaps on the first device, each labelled with the innermost
  benchmark span around its midpoint.

Timestamps of host and device events in one trace share one clock
(nanoseconds from the start of the profile).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
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


@dataclasses.dataclass
class Reduced:
    """What one trace says about its window."""

    window_s: float
    busy_s: float  # union of device-op intervals, mean over devices
    n_devices: int
    module_ns: "dict[str, float]"  # XLA module name -> summed ns
    op_ns: "dict[str, float]"  # device op name (as in the HLO) -> summed ns
    gaps: "list[tuple[str, float]]"  # (host span label, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> "float | None":
        """Device seconds of the modules whose name matches ``pattern``
        (``None`` when no such module ran)."""
        rx = re.compile(pattern)
        hits = [ns for name, ns in self.module_ns.items() if rx.search(name)]
        return sum(hits) / 1e9 if hits else None

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


def reduce_profile(profile) -> Reduced:
    spans: list[tuple[str, float, float]] = []
    devices: list = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0]
    if not devices:
        raise ValueError("trace holds no TPU device plane")

    module_ns: dict[str, float] = {}
    op_ns: dict[str, float] = {}
    busy = []
    first_union = None
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = ([lines[OPS_LINE]] if OPS_LINE in lines
                    else list(lines.values()))
        intervals = []
        for line in op_lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= w0 or a >= w1:
                    continue
                intervals.append((a, b))
                name = _op_name(ev.name)
                op_ns[name] = op_ns.get(name, 0.0) + (
                    min(b, w1) - max(a, w0)
                )
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= w0 or a >= w1:
                    continue
                name = _module_name(ev.name)
                module_ns[name] = module_ns.get(name, 0.0) + (
                    min(b, w1) - max(a, w0)
                )
        merged = _union(_clip(intervals, w0, w1))
        busy.append(sum(b - a for a, b in merged))
        if first_union is None:
            first_union = merged

    gaps = []
    edges = [w0] + [x for iv in first_union for x in iv] + [w1]
    inner = [(n, a, b) for n, a, b in spans if n != WINDOW_SPAN]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        around = [(sb - sa, n) for n, sa, sb in inner if sa <= mid <= sb]
        label = min(around)[1] if around else "no benchmark span"
        gaps.append((label, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        n_devices=len(devices),
        module_ns=module_ns,
        op_ns=op_ns,
        gaps=gaps,
    )
