"""Device idle time of one traced benchmark run, split by the program's
own ``elsar.*`` spans.

    python3 experiments/elsar_gaps.py --workload <cell> --seed <n> \
        [--seconds 30] [--out gaps.json] [--keep trace.xplane.pb]  # TPU
    python3 experiments/elsar_gaps.py --xplane trace.xplane.pb     # later

Runs one cell of ``BENCHMARK.json`` with ``--trace 1`` through the
benchmark's own entry point, in this process, and reads the trace the
run recorded before the benchmark removes it (``--keep`` copies it
first; ``--xplane`` reads a kept one again).  Every instant of device
idle time inside the window (first TPU device) goes to the innermost
``elsar.*`` span open at that instant, the shortest one of any host
thread, or to "no elsar span".  Reported: idle seconds per innermost
span, the share of idle time inside some ``elsar.*`` span over the
window and inside each ``bench.job`` span, and the share of the window
each span name covers.  Prints the benchmark's own output, then
one JSON line (also written to ``--out``).
"""

from __future__ import annotations

import argparse
import bisect
import heapq
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(1, os.path.join(ROOT, "src"))

from harness import main, runtime, tracereduce  # noqa: E402

SPAN_PREFIX = "elsar."
NO_SPAN = "no elsar span"
TOP = 12


def _overlap(merged, a, b) -> float:
    """Length of ``[a, b)`` covered by the merged intervals."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


def idle_gaps(profile):
    """``(window, gaps, spans, jobs)``: the window span, the idle gaps of
    the first device inside it, every ``elsar.*`` host span and every
    ``bench.job`` span, all as ``(start, end)`` ns on one clock."""
    spans, jobs, window, device = [], [], None, None
    for plane in profile.planes:
        if tracereduce.DEVICE_PLANE.match(plane.name):
            device = device or plane
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):], *iv))
                elif ev.name == tracereduce.WINDOW_SPAN and window is None:
                    window = iv
                elif ev.name == "bench.job":
                    jobs.append(iv)
    w0, w1 = window
    lines = {ln.name: ln for ln in device.lines}
    ops = lines.get(tracereduce.OPS_LINE) or next(iter(lines.values()))
    busy = tracereduce._union(tracereduce._clip(
        [(e.start_ns, e.start_ns + e.duration_ns) for e in ops.events],
        w0, w1))
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    return window, gaps, spans, [j for j in jobs if j[0] < w1 and j[1] > w0]


def by_innermost_span(gaps, spans) -> "dict[str, float]":
    """Idle ns of ``gaps`` per innermost ``elsar.*`` span: a sweep over
    every span and gap boundary, the shortest open span labelling each
    piece between two boundaries."""
    starts = sorted(gaps)
    g0 = [a for a, _ in starts]
    cum = [0.0]
    for a, b in starts:
        cum.append(cum[-1] + b - a)

    def idle_before(t):  # idle ns in the gaps before time t
        i = bisect.bisect_right(g0, t)
        if i == 0:
            return 0.0
        a, b = starts[i - 1]
        return cum[i - 1] + min(max(t - a, 0.0), b - a)

    events = sorted(
        [(s, 1, i) for i, (_, s, _) in enumerate(spans)]
        + [(e, 0, i) for i, (_, _, e) in enumerate(spans)]
        + [(x, 2, -1) for iv in gaps for x in iv]
    )
    out: dict[str, float] = {}
    heap: list = []  # (duration, index) of open spans, lazily deleted
    closed: set = set()
    prev = None
    for t, kind, i in events:
        if prev is not None and t > prev:
            while heap and heap[0][1] in closed:
                heapq.heappop(heap)
            label = spans[heap[0][1]][0] if heap else NO_SPAN
            idle = idle_before(t) - idle_before(prev)
            if idle:
                out[label] = out.get(label, 0.0) + idle
        prev = t
        if kind == 1:
            heapq.heappush(heap, (spans[i][2] - spans[i][1], i))
        elif kind == 0:
            closed.add(i)
    return out


def table(profile) -> dict:
    (w0, w1), gaps, spans, jobs = idle_gaps(profile)
    spans = [(n, max(s, w0), min(e, w1)) for n, s, e in spans
             if e > w0 and s < w1]
    covered = tracereduce._union([(s, e) for _, s, e in spans])
    idle = sum(b - a for a, b in gaps)
    in_spans = sum(_overlap(covered, a, b) for a, b in gaps)
    per_job = []
    for j0, j1 in jobs:
        jgaps = tracereduce._clip(gaps, j0, j1)
        jidle = sum(b - a for a, b in jgaps)
        jin = sum(_overlap(covered, a, b) for a, b in jgaps)
        per_job.append({
            "job_s": (j1 - j0) / 1e9,
            "idle_s": jidle / 1e9,
            "idle_in_elsar_pct": 100.0 * jin / jidle if jidle else None,
        })
    rows = sorted(by_innermost_span(gaps, spans).items(),
                  key=lambda kv: -kv[1])
    share = {}
    for name in {n for n, _, _ in spans}:
        u = tracereduce._union([(s, e) for n, s, e in spans if n == name])
        share[name] = 100.0 * sum(b - a for a, b in u) / (w1 - w0)
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": idle / 1e9,
        "idle_in_elsar_pct": 100.0 * in_spans / idle if idle else None,
        "idle_outside_elsar_pct_of_window":
            100.0 * (idle - in_spans) / (w1 - w0),
        "idle_by_innermost_span": [
            {"span": n, "seconds": ns / 1e9, "pct_of_idle": 100.0 * ns / idle}
            for n, ns in rows[:TOP]
        ],
        "window_pct_by_span": dict(sorted(share.items(),
                                          key=lambda kv: -kv[1])),
        "jobs": per_job,
    }


def _emit(found: dict, out: "str | None") -> None:
    line = json.dumps(found)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)


def run(argv) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out")
    ap.add_argument("--keep", help="copy the run's .xplane.pb here")
    ap.add_argument("--xplane", help="read a kept trace; run nothing")
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    if args.xplane:
        found = table(ProfileData.from_file(args.xplane))
        _emit(found, args.out)
        return found
    found: dict = {}
    reduce_trace = runtime.Run.reduce_trace

    def reduce_and_split(self):
        reduce_trace(self)
        path = tracereduce.find_xplane(self.trace_dir)
        if args.keep:
            os.makedirs(os.path.dirname(os.path.abspath(args.keep)),
                        exist_ok=True)
            shutil.copyfile(path, args.keep)
        found.update(table(ProfileData.from_file(path)))

    runtime.Run.reduce_trace = reduce_and_split
    rc = main.main(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", str(args.seconds), "--trace", "1"],
        t_start=T_START,
    )
    if rc:
        raise SystemExit(rc)
    found.update(workload=args.workload, seed=args.seed)
    _emit(found, args.out)
    return found


if __name__ == "__main__":
    run(sys.argv[1:])
