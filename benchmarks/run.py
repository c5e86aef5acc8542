"""Benchmark harness entry point — one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick]

Emits results/*.json and a console summary.
"""
from __future__ import annotations

import argparse
import time


def _section(title):
    print("\n" + "=" * 72)
    print(title)
    print("=" * 72, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: table4,fig2,fig3,fig4,ingest,scan")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    t_start = time.time()

    if only is None or "table4" in only:
        _section("Table 4 — runtime vs centralized (Luzzu-like) baseline")
        from . import table4_performance
        p = table4_performance.run(quick=args.quick)
        for row in p["table"]:
            n = row["n_triples"]
            if row.get("luzzu_joint_s") is not None:
                print(f"  {n:>9,} triples: luzzu(single)={row['luzzu_single_s']:7.2f}s "
                      f"luzzu(joint)={row['luzzu_joint_s']:7.2f}s "
                      f"dist(local)={row['dist_local_s']:6.3f}s "
                      f"speedup={row['speedup_vs_joint']:6.1f}x "
                      f"agree={row['correctness_agree']}")
            else:
                extra = (f" cluster8={row['dist_cluster8_s']:6.3f}s"
                         if "dist_cluster8_s" in row else "")
                print(f"  {n:>9,} triples: luzzu=(projected "
                      f"{row['luzzu_projected_joint_s']:8.1f}s) "
                      f"dist(local)={row['dist_local_s']:6.3f}s{extra} "
                      f"proj.speedup={row['projected_speedup']:7.1f}x")

    if only is None or "fig2" in only:
        _section("Fig 2 — size-up (fixed engine, growing data)")
        from . import fig2_sizeup
        p = fig2_sizeup.run(quick=args.quick)
        for r in p["rows"]:
            print(f"  {r['n_triples']:>9,} triples: {r['runtime_s']:7.3f}s "
                  f"({r['ns_per_triple']:6.1f} ns/triple)")
        print(f"  linear-fit R² = {p['linear_fit_r2']:.4f}")

    if only is None or "fig3" in only:
        _section("Fig 3 + Fig 5 — node scalability (1→N device mesh)")
        from . import fig3_node_scalability
        p = fig3_node_scalability.run(smoke=args.quick)
        for r in p["rows"]:
            print(f"  devices={r['devices']}: wall={r['wall_s']:7.3f}s "
                  f"S={r['speedup']:5.2f} E={r['efficiency']:5.2f} "
                  f"bit-identical={r['bit_identical']}")
        print(f"  ({p['method']})")

    if only is None or "fig4" in only:
        _section("Fig 4 — per-metric runtime + fused-pass §Perf headline")
        from . import fig4_per_metric
        p = fig4_per_metric.run(quick=args.quick)
        for n, d in p.items():
            print(f"  {int(n):,} triples:")
            for m, t in d["per_metric_s"].items():
                print(f"    {m:4s}: {t:6.3f}s")
            print(f"    paper mode (7 passes): {d['paper_mode_7_passes_s']:6.3f}s")
            print(f"    fused (1 pass):        {d['fused_1_pass_s']:6.3f}s "
                  f"-> {d['fusion_speedup']:4.1f}x")
            print(f"    fused, all 16 metrics: {d['fused_all_16_metrics_s']:6.3f}s")

    if only is None or "ingest" in only:
        _section("Ingest — legacy parse+encode vs vectorized rdf.ingest")
        from . import fig_ingest
        p = fig_ingest.run(smoke=args.quick)
        if p.get("speedup_at_largest_measured"):
            print(f"  headline: {p['speedup_at_largest_measured']:.1f}x at "
                  f"{p['n_triples_at_largest_measured']:,} triples "
                  f"(identical={p['all_identical']})")

    if only is None or "scan" in only:
        _section("Scan — passes over data + sync vs async executor")
        from . import fig_scan
        p = fig_scan.run(smoke=args.quick)
        print(f"  headline: fused_scan = "
              f"{p['fused_scan_passes_with_sketches']} pass(es) with "
              f"sketches; async speedup "
              f"{p['async_speedup_fused_scan']:.2f}x on streamed ingest "
              f"(identical={p['all_values_identical']}, "
              f"registers={p['hll_registers_bit_identical']})")

    print(f"\nTotal benchmark time: {time.time() - t_start:.1f}s")


if __name__ == "__main__":
    main()
