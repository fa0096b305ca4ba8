"""Benchmark harness — one entry per paper table/figure.

  fig6a/fig6b  accuracy + pruning ratios   (trained toy detector)
  fig7a        bank-conflict simulator     (inter- vs intra-level parallel)
  fig7b/fig8   MSGS memory-energy model    (fusion + fmap reuse)
  fig9/table1  platform comparison analogue (roofline from dry-run)
  micro        kernel wall-time micro-benches (CPU interpret, structural)

Prints ``name,us_per_call,derived`` CSV rows at the end."""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig6,fig7a,fig7b,fig9,fmap_reuse,"
                         "micro,decoder,serve")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write machine-readable rows "
                         "[{name, us_per_call, derived}, ...] to PATH "
                         "(for BENCH_*.json perf tracking)")
    args, _ = ap.parse_known_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows: list[tuple[str, float, str]] = []
    results: dict = {}

    def want(name: str) -> bool:
        return only is None or name in only

    if want("fig7a"):
        from benchmarks.bank_sim import simulate
        t0 = time.perf_counter()
        r = simulate()
        dt = (time.perf_counter() - t0) * 1e6
        results["fig7a_bank_sim"] = r
        rows.append(("fig7a_inter_vs_intra_throughput", dt,
                     f"ratio={r['throughput_ratio']:.2f}x "
                     f"(paper 3.06x), conflict_free={r['inter_conflict_free']}"))
        print(f"[fig7a] inter/intra throughput ratio "
              f"{r['throughput_ratio']:.2f}x (paper: 3.06x); "
              f"inter-level conflict-free: {r['inter_conflict_free']}")

    if want("fig7b"):
        from benchmarks.energy_model import model_energy
        t0 = time.perf_counter()
        e = model_energy()
        dt = (time.perf_counter() - t0) * 1e6
        results["fig7b_energy"] = e
        rows.append(("fig7b_energy_model", dt,
                     f"dram_fusion={e['dram_saving_fusion_pct']:.1f}% "
                     f"dram_reuse={e['dram_saving_reuse_pct']:.1f}%"))
        print(f"[fig7b] fusion: DRAM -{e['dram_saving_fusion_pct']:.1f}% "
              f"(paper 73.3%), SRAM -{e['sram_saving_fusion_pct']:.1f}% "
              f"(paper 15.9%)")
        print(f"[fig7b] reuse:  DRAM -{e['dram_saving_reuse_pct']:.1f}% "
              f"(paper 88.2%), SRAM -{e['sram_saving_reuse_pct']:.1f}% "
              f"(paper 22.7%)")

    if want("fig6"):
        from benchmarks.fig6_pruning import run as fig6_run
        t0 = time.perf_counter()
        r = fig6_run()
        dt = (time.perf_counter() - t0) * 1e6
        results["fig6"] = r
        ap_b = r["ap"]["baseline"]
        rows.append(("fig6a_ap_baseline", dt, f"AP={ap_b:.3f}"))
        for name, ap_v in r["ap"].items():
            if name != "baseline":
                rows.append((f"fig6a_ap_{name}", 0.0,
                             f"dAP={ap_v-ap_b:+.4f}"))
        red = r["reduction"]
        rows.append(("fig6b_reductions", 0.0,
                     f"pixels={red['fmap_pixels_pruned_pct']:.0f}% "
                     f"points={red['sampling_points_pruned_pct']:.0f}% "
                     f"compute={red['msgs_compute_saved_pct']:.0f}%"))

    if want("fig9"):
        from benchmarks.fig9_table1 import run as fig9_run
        t0 = time.perf_counter()
        r = fig9_run()
        dt = (time.perf_counter() - t0) * 1e6
        results["fig9_table1"] = r
        if "defa_vs_baseline_speedup" in r:
            rows.append(("fig9_defa_speedup", dt,
                         f"{r['defa_vs_baseline_speedup']:.2f}x roofline"))

    if want("fmap_reuse"):
        from benchmarks.fmap_reuse import report as reuse_report
        t0 = time.perf_counter()
        r = reuse_report()
        dt = (time.perf_counter() - t0) * 1e6
        results["fmap_reuse_vmem"] = r
        rows.append(("fmap_reuse_vmem_ratio", dt,
                     f"window kernel VMEM {r['total_vmem_full_kb']:.0f}KB->"
                     f"{r['total_vmem_window_kb']:.0f}KB "
                     f"({r['total_ratio']:.1f}x smaller working set)"))
        rows.append(("fmap_reuse_decoder_cache", 0.0,
                     f"{r['decoder_layers']}-layer decoder staged bytes "
                     f"{r['decoder_rebuild_kb']:.0f}KB rebuild-per-layer -> "
                     f"{r['decoder_cache_once_kb']:.0f}KB build-once "
                     f"({r['decoder_reuse_ratio']:.1f}x)"))
        rows.append(("fmap_reuse_table_dtype", 0.0,
                     f"value table f32 {r['table_f32_kb']:.0f}KB -> int8 "
                     f"{r['table_int8_kb']:.0f}KB per build "
                     f"({r['table_dtype_ratio']:.2f}x staged-bytes)"))
        rows.append(("fmap_reuse_stream", 0.0,
                     f"{r['stream_frames']}-frame drifting scene staged "
                     f"bytes {r['stream_rebuild_total_kb']:.0f}KB "
                     f"rebuild-per-frame -> "
                     f"{r['stream_staged_total_kb']:.0f}KB incremental "
                     f"({r['stream_bytes_ratio']:.2f}x measured, "
                     f"{r['stream_rebuild_frames']} rebuild frames)"))
        print(f"[fmap-reuse] windowed kernel working set: "
              f"{r['total_vmem_full_kb']:.0f} KB -> "
              f"{r['total_vmem_window_kb']:.0f} KB ({r['total_ratio']:.1f}x)")
        print(f"[fmap-reuse] decoder ValueCache ({r['decoder_layers']} "
              f"layers): {r['decoder_rebuild_kb']:.0f} KB rebuild -> "
              f"{r['decoder_cache_once_kb']:.0f} KB build-once "
              f"({r['decoder_reuse_ratio']:.1f}x)")
        print(f"[fmap-reuse] table dtype: f32 {r['table_f32_kb']:.0f} KB -> "
              f"int8 {r['table_int8_kb']:.0f} KB per build "
              f"({r['table_dtype_ratio']:.2f}x staged-bytes)")
        print(f"[fmap-reuse] streaming ({r['stream_frames']} frames, "
              f"measured): {r['stream_rebuild_total_kb']:.0f} KB "
              f"rebuild-per-frame -> {r['stream_staged_total_kb']:.0f} KB "
              f"incremental ({r['stream_bytes_ratio']:.2f}x)")

    if want("decoder"):
        from benchmarks.detr_toy import (eval_ap, train_toy_decoder_detector,
                                         with_attn)
        t0 = time.perf_counter()
        dcfg, dparams = train_toy_decoder_detector()
        ap_dec = eval_ap(dcfg, dparams)
        dt = (time.perf_counter() - t0) * 1e6
        defa_cfg = with_attn(dcfg, pap_mode="topk", pap_keep=6,
                             fwp_mode="compact", fwp_k=1.0, fwp_capacity=0.6,
                             range_narrow=(8.0, 6.0, 4.0, 3.0),
                             act_bits=12, weight_bits=12)
        ap_defa = eval_ap(defa_cfg, dparams)
        results["decoder_head"] = {
            "ap": ap_dec, "ap_defa": ap_defa,
            "n_layers": dcfg.decoder.n_layers,
            "n_queries": dcfg.decoder.n_queries,
        }
        rows.append(("decoder_head_ap", dt,
                     f"AP={ap_dec:.3f} (DEFA stack {ap_defa:.3f}), "
                     f"{dcfg.decoder.n_layers} layers x "
                     f"{dcfg.decoder.n_queries} queries, shared ValueCache"))
        print(f"[decoder] toy synthetic-task AP with the decoder head: "
              f"{ap_dec:.3f} (with the full DEFA stack: {ap_defa:.3f})")

    if want("serve"):
        from benchmarks.serve_sustained import report as serve_report
        t0 = time.perf_counter()
        r = serve_report()
        dt = (time.perf_counter() - t0) * 1e6
        results["serve_sustained"] = r
        cl, ol = r["closed_loop"], r["open_loop"]
        rows.append(("serve_sustained_speedup", dt,
                     f"{cl['speedup']:.2f}x vs single-bucket sync; "
                     f"{ol['rps_per_chip']} req/s/chip, "
                     f"P50 {ol['p50_ms']}ms P99 {ol['p99_ms']}ms"))

    if want("micro"):
        from benchmarks.microbench import run as micro_run
        micro_rows = micro_run()
        rows.extend(micro_rows)
        # per-backend micro rows keyed by name: the CI regression gate
        # (benchmarks/check_regression.py) diffs these against the
        # committed results/benchmarks.json baseline
        results["micro"] = {n: {"us_per_call": round(us, 1), "derived": d}
                            for n, us, d in micro_rows}

    os.makedirs("results", exist_ok=True)
    # merge: a partial run (--only micro) must not clobber the other
    # figures' entries in the committed baseline
    baseline: dict = {}
    if os.path.exists("results/benchmarks.json"):
        try:
            with open("results/benchmarks.json") as f:
                baseline = json.load(f)
        except (OSError, ValueError):
            baseline = {}
    baseline.update(results)
    with open("results/benchmarks.json", "w") as f:
        json.dump(baseline, f, indent=1, default=str)

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if args.json:
        payload = {
            "rows": [{"name": n, "us_per_call": round(us, 1), "derived": d}
                     for n, us, d in rows],
            "results": results,
        }
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, default=str)
        print(f"[run] wrote {len(payload['rows'])} rows to {args.json}")


if __name__ == "__main__":
    main()
