"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines (+ roofline lines when the
dry-run artifacts exist).

``--format {fixed,line,all}`` (or ``REPRO_BENCH_FORMAT``) selects the
record-layout axis: ``fixed`` runs the historical gensort figures,
``line`` the variable-length newline-corpus rates (DESIGN.md §8), ``all``
both.

``--op {none,ops,all}`` (or ``REPRO_BENCH_OP``) adds the merge-free
operator axis (``benchmarks/join_rates.py``: join selectivity x dup
factor, DESIGN.md §9).

``--json PATH`` runs the **bench-smoke** collection instead of the
figure suites: sort + query + operator rates on the fixed-seed corpus,
written as one machine-readable JSON (the ``BENCH_ci.json`` artifact the
CI job uploads so the perf trajectory accumulates per PR) plus a
one-line rates summary on stdout."""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def smoke(
    n: int,
    json_path: str,
    dist: str = "core",
    sweep_sizes: "list[int] | None" = None,
    mesh_n: int = 0,
    writers: "list[int] | None" = None,
) -> None:
    """Collect sort + query + operator + executor rates into one JSON
    artifact (``benchmarks/check_regression.py`` diffs it against the
    committed ``BENCH_*.json`` baseline).  ``dist="adversarial"``
    additionally runs the hostile-corpus rows (DESIGN.md §11) so the
    planner's decisions land in ``BENCH_ci.json``; ``sweep_sizes``
    (``--records`` comma list) adds the ELSAR-vs-mergesort corpus-size
    sweep and its ``crossover_records`` (DESIGN.md §12)."""
    from benchmarks import join_rates, query_rates, sort_rates

    data = {
        "schema": 3,
        "records": n,
        "sort": sort_rates.run(n),
        "query": query_rates.run(n),
        "ops": join_rates.run(n),
        # device-executor axis (DESIGN.md §10): batched super-batches vs
        # the per-partition dispatch baseline
        "executor": sort_rates.run_executor(n),
        # serve axis (DESIGN.md §14): open-loop qps sweep, serial vs
        # continuous-batching dispatch + the overload shed probe — on
        # the acceptance corpus size regardless of REPRO_BENCH_RECORDS
        "serve": query_rates.run_open_loop(min(n, 100_000)),
    }
    if dist == "adversarial":
        data["adversarial"] = sort_rates.run_adversarial(n)
    if sweep_sizes:
        data["sweep"] = sort_rates.run_sweep(sweep_sizes)
    if mesh_n:
        # distributed axis (DESIGN.md §13): host vs mesh-batched final
        # pass over an N-device data mesh (main() fakes the devices)
        data["mesh"] = sort_rates.run_mesh(n, mesh_n)
    if writers:
        # storage axis (DESIGN.md §15): writer-pool scaling on the
        # forced-spill corpus, rates relative to measured disk bandwidth
        data["writer_scaling"] = sort_rates.run_writers(n, writers)
    with open(json_path, "w") as f:
        json.dump(data, f, indent=2, default=float)
    sort_mb = max(
        r["rate_mb_s"] for r in data["sort"] if r["algo"] == "elsar"
    )
    qps = max(r["qps"] for r in data["query"])
    join_mb = max(
        r["rate_mb_s"] for r in data["ops"] if r["op"] == "join"
    )
    disp = {r["executor"]: r["dispatches"] for r in data["executor"]}
    adv = "".join(
        f" {r['dist']}={r['planner_decision']}"
        for r in data.get("adversarial", ())
    )
    xover = (
        f" crossover={data['sweep']['crossover_records']}"
        if "sweep" in data
        else ""
    )
    mesh_s = "".join(
        f" mesh_{r['executor']}={r['rate_mb_s']:.1f}MB/s"
        for r in data.get("mesh", ())
    )
    wrt = ""
    if data.get("writer_scaling"):
        wrows = data["writer_scaling"]
        top = max(wrows, key=lambda r: r["n_writers"])
        wrt = (
            f" writers_x{top['n_writers']}={top['vs_single']:.2f}x"
            f"{'(io_bound)' if top['io_bound'] else ''}"
        )
    srv = data["serve"]
    print(
        f"bench-smoke: records={n} sort={sort_mb:.1f}MB/s "
        f"query={qps:.0f}q/s join={join_mb:.1f}MB/s "
        f"dispatches={disp.get('batched')}/{disp.get('per_partition')} "
        f"(batched/per-partition) "
        f"serve={srv['batched_capacity_qps']:.0f}q/s@p99<"
        f"{srv['slo_ms']:.0f}ms ({srv['speedup']:.1f}x serial, "
        f"overload_shed={srv['overload']['shed']})"
        f"{adv}{xover}{mesh_s}{wrt} -> {json_path}"
    )


def _peek_mesh(argv: "list[str]") -> int:
    """Extract ``--mesh N`` before anything imports jax: faking host
    devices only works if XLA_FLAGS is set before backend init."""
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--mesh="):
            return int(a.split("=", 1)[1])
    return int(os.environ.get("REPRO_BENCH_MESH", "0") or 0)


def main(argv: "list[str] | None" = None) -> None:
    argv = argv if argv is not None else sys.argv[1:]
    mesh_n = _peek_mesh(argv)
    if mesh_n > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={mesh_n}"
        ).strip()
    from repro.launch import compile_cache

    compile_cache.enable()
    from benchmarks import (
        io_stats,
        join_rates,
        joulesort,
        partition_variance,
        phase_breakdown,
        query_rates,
        scalability,
        sort_rates,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--format",
        choices=("fixed", "line", "all"),
        default=os.environ.get("REPRO_BENCH_FORMAT", "fixed"),
        help="record-layout axis (default: fixed gensort figures)",
    )
    ap.add_argument(
        "--op",
        choices=("none", "ops", "all"),
        default=os.environ.get("REPRO_BENCH_OP", "none"),
        help="merge-free operator axis (join/dedup/groupby rates)",
    )
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="bench-smoke mode: write sort+query+op rates as JSON",
    )
    ap.add_argument(
        "--records",
        default=os.environ.get("REPRO_BENCH_SWEEP", ""),
        metavar="N1,N2,...",
        help="bench-smoke corpus-size sweep: comma list of record counts "
        "for the elsar-vs-extms crossover axis (DESIGN.md §12)",
    )
    ap.add_argument(
        "--dist",
        choices=("core", "adversarial"),
        default=os.environ.get("REPRO_BENCH_DIST", "core"),
        help="corpus axis for bench-smoke: core distributions only, or "
        "additionally the hostile planner corpora (DESIGN.md §11)",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=0,
        metavar="N",
        help="bench-smoke distributed axis: run sort_file_distributed "
        "over an N-device data mesh (fakes N host devices; DESIGN.md §13)",
    )
    ap.add_argument(
        "--writers",
        default=os.environ.get("REPRO_BENCH_WRITERS", ""),
        metavar="W1,W2,...",
        help="bench-smoke storage axis: writer-pool widths to scale over "
        "on the forced-spill corpus (DESIGN.md §15), e.g. 1,4",
    )
    args = ap.parse_args(argv)
    if args.format not in ("fixed", "line", "all"):
        # argparse does not validate defaults, so a typo'd
        # REPRO_BENCH_FORMAT must fail loudly, not select zero suites
        ap.error(f"invalid REPRO_BENCH_FORMAT {args.format!r}")
    if args.op not in ("none", "ops", "all"):
        ap.error(f"invalid REPRO_BENCH_OP {args.op!r}")
    if args.dist not in ("core", "adversarial"):
        ap.error(f"invalid REPRO_BENCH_DIST {args.dist!r}")

    n = int(os.environ.get("REPRO_BENCH_RECORDS", 1_000_000))
    sweep = (
        [int(s) for s in args.records.split(",") if s.strip()]
        if args.records
        else None
    )
    writers = (
        sorted({int(s) for s in args.writers.split(",") if s.strip()})
        if args.writers
        else None
    )
    if args.json:
        smoke(n, args.json, dist=args.dist, sweep_sizes=sweep,
              mesh_n=mesh_n, writers=writers)
        return
    # explicit argv/args: the harness's own sys.argv must never leak into a
    # suite's argparse, and REPRO_BENCH_RECORDS scales every suite that
    # takes a record count (Fig. 4's sizes are structural: budget multiples)
    suites = []
    if args.format in ("fixed", "all"):
        suites += [
            ("fig2_sort_rates", lambda: sort_rates.main(n)),
            ("s33_fig3_partition_variance",
             lambda: partition_variance.main(n)),
            ("fig4_scalability", lambda: scalability.main([])),
            ("fig5_joulesort", lambda: joulesort.main(n)),
            ("fig6_phase_breakdown", lambda: phase_breakdown.main(
                ["--records", str(n)])),
            ("fig7_io_stats", lambda: io_stats.main(n)),
            ("serve_query_rates", lambda: query_rates.main(n)),
        ]
    if args.format in ("line", "all"):
        suites += [
            ("line_sort_rates", lambda: sort_rates.main_line(n)),
        ]
    if args.op in ("ops", "all"):
        suites += [
            ("op_join_rates", lambda: join_rates.main(n)),
        ]
    failures = 0
    for name, fn in suites:
        try:
            fn()
        except Exception:
            failures += 1
            print(f"{name},NaN,ERROR", file=sys.stderr)
            traceback.print_exc()

    # roofline lines (from dry-run artifacts, if present): baseline + opt
    base = os.path.join(os.path.dirname(__file__), "..", "experiments")
    for tag, sub in (("base", "dryrun"), ("opt", "dryrun_opt")):
        dr = os.path.join(base, sub)
        if not os.path.isdir(dr):
            continue
        try:
            from benchmarks import roofline

            for r in roofline.load(dr):
                print(
                    f"roofline_{tag}_{r['arch']}_{r['shape']}_{r['mesh']},0.0,"
                    f"dom={r['bottleneck']} useful={100*r['useful_compute_frac']:.0f}% "
                    f"useful_mfu={100*r['useful_mfu']:.1f}%"
                )
        except Exception:
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
