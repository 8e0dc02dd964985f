"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus a roofline summary from
the dry-run artifacts when present).

``--suite index_build`` runs the index-construction perf suite instead and
writes ``BENCH_index_build.json`` (build wall time + peak-intermediate
estimate per mode for n in {1e4, 1e5, 1e6}) — the artifact CI tracks for
the perf trajectory of ``build_index``.

``--suite serve`` runs the query-serving suite (warmed SuCoEngine behind
the continuous micro-batching AnnServer) and writes ``BENCH_serve.json``
(QPS + p50/p99 latency per traffic mix for the legacy streaming engine
*and* the fused single-pass engine — the ``fused`` section tracks the
per-mix speedup — zero-retrace-after-warmup asserted for both).  ``--suite serve_async`` is the pipelined-serving slice of the
same collection: sync-vs-async replay per mix, the traffic-driven bucket
autoscale consumption path, and the heterogeneous-k sharded pool — the
zero-retrace invariant asserted on all three.  ``--suite serve_chaos``
runs the resilience smoke (``BENCH_serve_chaos.json``): a forced
degrade/recover walk down the degradation ladder with
``retraces_after_warmup == 0`` asserted, plus the flood-overload replay
comparing admission control + degradation against an uncontrolled
server.  ``--suite serve_mutation`` runs the live-mutation lane (insert
throughput, tombstone-delete visibility, warm re-index handoff with a
~0 swap pause and recall before/after the re-cluster) and merges a
``mutation`` section into ``BENCH_serve.json`` — run it after ``serve``
so one artifact carries the whole serving trajectory.
``--suite serve_recovery`` runs the durability lane (snapshot wall time,
cold-recovery wall time and WAL replay rate vs log length, and the
crash-drill assertion pass over every instrumented boundary) and merges
a ``recovery`` section into the same artifact.  ``--toy`` is the CI
smoke form for any of these: shrunk sizes, writes the ``*.toy.json``
artifact.
"""

from __future__ import annotations

import sys
import traceback

MODULES = (
    "benchmarks.fig2_pareto",
    "benchmarks.table2_sc_linear",
    "benchmarks.table4_suco_vs_linear",
    "benchmarks.table5_l1_l2",
    "benchmarks.fig6_da_vs_ms",
    "benchmarks.fig7_k_ns",
    "benchmarks.fig8_alpha_beta",
    "benchmarks.fig9_12_competitors",
    "benchmarks.fig14_preprocessing",
    "benchmarks.micro_merge_pool",
)

# suite name -> "module" (entry point `run`) or "module:function"
SUITES = {
    "index_build": "benchmarks.index_build",
    "serve": "benchmarks.serve",
    "serve_async": "benchmarks.serve:run_async",
    "serve_chaos": "benchmarks.serve_chaos",
    "serve_mutation": "benchmarks.serve_mutation",
    "serve_recovery": "benchmarks.serve_recovery",
}


def _run_suite(name: str, extra: list[str]) -> None:
    import importlib
    import inspect

    if name not in SUITES:
        raise SystemExit(f"unknown suite {name!r}; available: {sorted(SUITES)}")
    modname, _, fn_name = SUITES[name].partition(":")
    fn = getattr(importlib.import_module(modname), fn_name or "run")
    kwargs = {}
    if "--toy" in extra:
        if "toy" not in inspect.signature(fn).parameters:
            raise SystemExit(f"suite {name!r} does not support --toy")
        kwargs["toy"] = True
    print("name,us_per_call,derived")
    for row_name, us, derived in fn(**kwargs):
        print(f"{row_name},{us:.1f},{derived}", flush=True)


def main() -> None:
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = sys.argv[1:]
    if "--suite" in argv:
        idx = argv.index("--suite")
        if idx + 1 >= len(argv):
            raise SystemExit("--suite requires a name (e.g. index_build)")
        _run_suite(argv[idx + 1], argv[idx + 2:])
        return
    only = argv[0] if argv else None
    print("name,us_per_call,derived")
    failed = 0
    for modname in MODULES:
        if only and only not in modname:
            continue
        try:
            mod = importlib.import_module(modname)
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:
            failed += 1
            print(f"{modname},ERROR,", flush=True)
            traceback.print_exc(file=sys.stderr)

    # roofline summary (reads benchmarks/results/*.json if the dry-run ran)
    try:
        from benchmarks.roofline import load_cells, roofline_row

        for rec in load_cells():
            row = roofline_row(rec)
            if row is None:
                continue
            name = f"roofline/{row['arch']}/{row['shape']}/{row['mesh']}"
            derived = (
                f"dominant={row['dominant']};compute_s={row['compute_s']:.4e};"
                f"memory_s={row['memory_s']:.4e};collective_s={row['collective_s']:.4e};"
                f"useful={row['useful_ratio']:.2f}"
            )
            step = max(row['compute_s'], row['memory_s'], row['collective_s'])
            print(f"{name},{step*1e6:.1f},{derived}")
    except Exception:
        traceback.print_exc(file=sys.stderr)

    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
