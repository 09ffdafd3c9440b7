"""Benchmark harness entry point: one module per paper table/finding.

    PYTHONPATH=src python -m benchmarks.run

  table1          — paper Table 1 (resources + GOP/s, 3 ZYNQ boards)
  table2          — paper Table 2 (vs Bjerge et al. on Ultra96)
  dse_sweep       — paper §III.E tau≈2mu finding + TPU block DSE
  kernel_table    — Pallas compute-unit structural metrics + oracle check
  precision_drift — fixed-point drift + per-layer precision DSE sweep (§8/§11)
  scheduler_soak  — continuous-batching mixed-trace soak (virtual clock)
  router_soak     — multi-process replica fleet + injected kill (§9)
  roofline_report — §Roofline table from the dry-run cache (if present)

The per-module rows are consolidated into ``BENCH_pr10.json`` at the repo
root (one object per module that returned JSON-serializable rows).
"""
from __future__ import annotations

import json
import os
import sys
import traceback


def main():
    from repro.core.engine import (
        plan_store_stats,
        save_plan_store,
        warm_start_plan_store,
    )

    store_path, n = warm_start_plan_store()
    warm = n > 0
    if warm:
        print(f"[plan-store] warm-started {n} entries from {store_path}")

    failures = []
    results = {}
    for name in ("table1", "table2", "dse_sweep", "kernel_table",
                 "precision_drift", "scheduler_soak", "router_soak"):
        print("\n" + "=" * 72)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            out = mod.main()
        except Exception:
            traceback.print_exc()
            failures.append(name)
        else:
            try:
                json.dumps(out)
            except (TypeError, ValueError):
                continue
            if out is not None:
                results[name] = out

    for label, d in (("baseline", "experiments/dryrun"),
                     ("optimized", "experiments/dryrun_opt")):
        print("\n" + "=" * 72)
        print(f"== Roofline ({label}) ==")
        try:
            from benchmarks import roofline_report

            if not os.path.isdir(d):
                print(f"(no {d} — run repro.launch.dryrun first)")
                continue
            rows = roofline_report.main(["--mesh", "16x16", "--dir", d])
            if rows:
                print(f"\n({label} roofline rows: {len(rows)} single-pod cells)")
        except Exception:
            traceback.print_exc()
            failures.append(f"roofline_report:{label}")
    st = plan_store_stats()
    print(f"\n[plan-store] this run: {st['gemm_blocks']} GEMM blocks + "
          f"{st['conv_tiles']} conv tiles in registry, "
          f"{st['misses']} new DSE searches, {st['hits']} cache hits")
    if os.environ.get("REPRO_PLAN_ASSERT_WARM") == "1":
        # CI warm-start gate: a run against a populated store must not search.
        # Checked *before* saving — persisting the newly searched entries on
        # a failing gate would make a retry self-heal and mask the regression.
        if not warm:
            print("[plan-store] ASSERT_WARM set but no store was loaded")
            sys.exit(1)
        if st["misses"] > 0:
            print(f"[plan-store] warm-start FAILED: {st['misses']} DSE searches "
                  "ran against a populated store")
            sys.exit(1)
        print("[plan-store] warm-start OK: zero DSE searches")
    if store_path:
        save_plan_store(store_path)
        print(f"[plan-store] saved to {store_path}")
    bench_out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_pr10.json")
    try:
        with open(bench_out, "w") as f:
            json.dump(results, f, indent=2, default=str)
        print(f"[bench] consolidated results for {sorted(results)} "
              f"-> {bench_out}")
    except Exception:
        traceback.print_exc()
        failures.append("BENCH_pr10.json")
    if failures:
        print(f"\nbenchmark FAILURES: {failures}")
        sys.exit(1)
    print("\nall benchmarks completed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
