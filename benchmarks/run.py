"""Benchmark harness entry point — one module per paper table/claim.

  table2_methods   — paper Table II  (4 methods on the DR task)
  table3_archs     — paper Table III (model-agnostic CNN sweep)
  comm_scaling     — §I/§III.B scalability & communication claim
  cluster_ablation — beyond-paper k / p1 / p2 ablation
  churn_bench      — dropout x stale-decay robustness sweep (one program)
  hier_bench       — two-tier coordination: O(pods) upload scaling + the
                     pods==1 bitwise anchor (BENCH_hier.json)
  bucket_bench     — ragged bucketed layout vs rectangular pad-to-max
  kernel_bench     — kernel-layer microbenchmarks
  roofline_report  — §Roofline table from the dry-run artifacts
  serve_bench      — continuous-batching engine: throughput/latency vs
                     bucket layout + the per-bucket program budget

Each row prints ``name,us_per_call,derived`` CSV.
Usage: PYTHONPATH=src python -m benchmarks.run [--only name] [--fast]
"""
from __future__ import annotations

import argparse
import time

from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true",
                    help="smaller data scale for quick runs")
    ap.add_argument("--quick", action="store_true",
                    help="sweep + grid smoke only: the Table-II method "
                         "axis as one run_sweep program and the k x p1 "
                         "ablation as one run_grid program, both at "
                         "--data-scale CPU size")
    ap.add_argument("--data-scale", type=int, default=16,
                    help="Table-I divisor for --quick/--fast runs")
    ap.add_argument("--no-artifacts", action="store_true",
                    help="never (re)write BENCH_*.json — the CI smoke "
                         "guard (--quick already writes none; this also "
                         "covers the full/--fast suites)")
    args = ap.parse_args()
    use_compile_cache()

    if args.quick:
        from benchmarks import (churn_bench, cluster_ablation, hier_bench,
                                serve_bench, table2_methods)
        print("name,us_per_call,derived")
        table2_methods.run(data_scale=args.data_scale, rounds=2,
                           local_steps=2, image_size=16,
                           serial_reference=False)
        cluster_ablation.grid_bench(data_scale=args.data_scale, rounds=2,
                                    local_steps=2, serial_reference=False,
                                    out_json=None)
        churn_bench.run(data_scale=args.data_scale, rounds=2,
                        local_steps=2, dropouts=(0.0, 0.4),
                        stale_decays=(0.0, 0.5), out_json=None)
        serve_bench.run(n_requests=6, max_new=4, max_seq=32, slots=4,
                        cnn_requests=6, cnn_buckets=(1, 4), out_json=None)
        # two-tier smoke: small Ns, same invariants (O(pods) slope vs
        # ledger, pods==1 bitwise, compile census), no artifact
        hier_bench.run(ns=(128, 256), pod_size=32, rounds=2,
                       local_steps=2, out_json=None)
        return

    from benchmarks import (bucket_bench, churn_bench, cluster_ablation,
                            comm_scaling, hier_bench, kernel_bench,
                            roofline_report, serve_bench, table2_methods,
                            table3_archs)

    suites = {
        "comm_scaling": comm_scaling.main,
        "kernel_bench": kernel_bench.main,
        "roofline_report": roofline_report.main,
        "table2_methods": table2_methods.main,
        "table3_archs": table3_archs.main,
        "cluster_ablation": lambda: (cluster_ablation.grid_bench(),
                                     cluster_ablation.run()),
        "churn_bench": churn_bench.main,
        "bucket_bench": bucket_bench.main,
        "hier_bench": hier_bench.main,
        "serve_bench": serve_bench.main,
    }
    if args.fast:
        scale = args.data_scale
        suites["table2_methods"] = lambda: table2_methods.run(
            data_scale=scale, rounds=2, local_steps=4)
        suites["table3_archs"] = lambda: table3_archs.run(
            data_scale=scale, rounds=2, local_steps=4)
        suites["cluster_ablation"] = lambda: (
            cluster_ablation.grid_bench(data_scale=scale, rounds=2,
                                        local_steps=4, out_json=None),
            cluster_ablation.run(data_scale=scale, rounds=2, local_steps=4))
        suites["churn_bench"] = lambda: churn_bench.run(
            data_scale=scale, rounds=2, local_steps=4,
            dropouts=(0.0, 0.4), stale_decays=(0.0, 0.5), out_json=None)
        suites["bucket_bench"] = lambda: bucket_bench.run(
            data_scale=scale, rounds=2, local_steps=4, out_json=None)
        suites["hier_bench"] = lambda: hier_bench.run(
            ns=(128, 256), pod_size=32, rounds=2, local_steps=4,
            out_json=None)
        suites["serve_bench"] = lambda: serve_bench.run(
            n_requests=8, max_new=4, max_seq=32, slots=4,
            cnn_requests=8, out_json=None)
    if args.no_artifacts and not args.fast:
        # --fast is already write-free (its overrides above pass
        # bench_json/out_json=None); only the full suite's writers —
        # table2_methods.main (BENCH_sweep.json), the default grid_bench
        # (BENCH_grid.json), churn_bench (BENCH_churn.json), bucket_bench
        # (BENCH_bucket.json), hier_bench (BENCH_hier.json) and
        # serve_bench (BENCH_serve.json) — need the artifact-free
        # variant of the SAME measurement
        suites["table2_methods"] = lambda: table2_methods.run(
            paper_budget_oracle=True)
        suites["cluster_ablation"] = lambda: (
            cluster_ablation.grid_bench(out_json=None),
            cluster_ablation.run())
        suites["churn_bench"] = lambda: churn_bench.run(out_json=None)
        suites["bucket_bench"] = lambda: bucket_bench.run(out_json=None)
        suites["hier_bench"] = lambda: hier_bench.run(out_json=None)
        suites["serve_bench"] = lambda: serve_bench.run(out_json=None)

    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"suite/{name},{(time.time()-t0)*1e6:.0f},status=ok")
        except Exception as e:  # noqa: BLE001
            print(f"suite/{name},{(time.time()-t0)*1e6:.0f},status=FAIL:{e!r}")
            raise


if __name__ == '__main__':
    main()
