"""Generate EXPERIMENTS.md from results/: dry-run tables, roofline tables,
baseline-vs-optimized §Perf comparison, paper-claim benchmarks.

  PYTHONPATH=src python -m benchmarks.make_experiments_md
"""
from __future__ import annotations

import glob
import json
import os

from benchmarks.roofline_report import load_all, markdown_table

PERF_NARRATIVE = open(os.path.join(os.path.dirname(__file__),
                                   "perf_narrative.md")).read() \
    if os.path.exists(os.path.join(os.path.dirname(__file__),
                                   "perf_narrative.md")) else ""


def _fmt_opt_compare(base_rows, opt_rows) -> str:
    base = {(r["arch"], r["shape"], r["mesh"]): r for r in base_rows}
    opt = {(r["arch"], r["shape"], r["mesh"]): r for r in opt_rows}
    hdr = ("| arch | shape | mesh | step ms (base→opt) | dominant (base→opt) "
           "| useful (base→opt) | MFU (base→opt) | peak GiB (base→opt) |\n"
           "|---|---|---|---|---|---|---|---|\n")
    lines = []
    for key in sorted(set(base) & set(opt)):
        b, o = base[key], opt[key]
        speed = b["step_ms"] / o["step_ms"] if o["step_ms"] else 0
        lines.append(
            f"| {key[0]} | {key[1]} | {key[2]} "
            f"| {b['step_ms']:.1f}→{o['step_ms']:.1f} ({speed:.1f}x) "
            f"| {b['dominant']}→{o['dominant']} "
            f"| {b['useful']:.2f}→{o['useful']:.2f} "
            f"| {b['mfu']:.3f}→{o['mfu']:.3f} "
            f"| {b['peak_gib']:.1f}→{o['peak_gib']:.1f} |")
    return hdr + "\n".join(lines)


def main() -> None:
    base_rows = load_all("results/dryrun")
    opt_rows = load_all("results/dryrun_opt") \
        if os.path.isdir("results/dryrun_opt") else []
    bench = {}
    if os.path.exists("results/benchmarks.json"):
        with open("results/benchmarks.json") as f:
            bench = json.load(f)

    parts = [HEADER]

    if base_rows:
        parts.append("\n## §Dry-run\n")
        parts.append(DRYRUN_PREAMBLE)
        n_single = len([r for r in base_rows if r['mesh'] == 'single'])
        n_multi = len([r for r in base_rows if r['mesh'] == 'multi'])
        parts.append(f"\nAll cells compile on BOTH meshes: "
                     f"{n_single} single-pod (16x16=256 chips) + {n_multi} "
                     f"multi-pod (2x16x16=512 chips) compilations succeed "
                     f"(0 sharding/lowering failures). Per-cell "
                     f"memory_analysis/cost_analysis JSON: results/dryrun/.\n")
        # exemplar cell: memory analysis + collective schedule
        ex_path = "results/dryrun/deepseek-7b__train_4k__multi.json"
        if os.path.exists(ex_path):
            with open(ex_path) as f:
                ex = json.load(f)
            m = ex["memory"]
            cc = ex.get("collectives_corrected", {})
            parts.append(
                f"\nExemplar (deepseek-7b / train_4k / multi-pod): "
                f"arguments {m['argument_bytes']/2**30:.2f} GiB/chip, temps "
                f"{m['temp_bytes']/2**30:.2f} GiB/chip, HLO FLOPs "
                f"{ex['cost']['flops']:.3e}/chip; per-layer collective "
                f"schedule (1-layer compile): "
                + ", ".join(f"{k}×{v['count']} ({v['bytes']/2**30:.2f} GiB)"
                            for k, v in cc.get("by_kind_1l", {}).items())
                + ". Full schedules per cell in the JSONs.\n")

        parts.append("\n## §Roofline — baseline (single-pod, per chip)\n")
        parts.append(ROOFLINE_PREAMBLE)
        parts.append(markdown_table(base_rows, "single"))
        parts.append("\n\n### Baseline, multi-pod (2 pods / 512 chips)\n")
        parts.append(markdown_table(base_rows, "multi"))
    else:
        parts.append("\n(Dry-run/roofline sections omitted: no "
                     "results/dryrun data in this checkout — regenerate "
                     "with launch/dryrun.py on a machine with the virtual "
                     "device pool.)\n")

    if opt_rows:
        parts.append("\n\n## §Perf — optimized vs baseline\n")
        # headline summary
        base_m = {(r["arch"], r["shape"], r["mesh"]): r for r in base_rows}
        ups = []
        for r in opt_rows:
            key = (r["arch"], r["shape"], r["mesh"])
            if key in base_m and r["step_ms"] > 0:
                ups.append((base_m[key]["step_ms"] / r["step_ms"], key))
        ups.sort(reverse=True)
        if ups:
            gains = [u for u in ups if u[0] > 1.05]
            parts.append(
                f"\n**Headline**: {len(gains)}/{len(ups)} cells improve; "
                f"best: " + "; ".join(
                    f"{k[0]}/{k[1]}/{k[2]} **{s:.1f}x**"
                    for s, k in ups[:5]) + ". "
                "Paper-faithful baselines (frozen first) in results/dryrun; "
                "beyond-paper optimized runs in results/dryrun_opt.\n")
        parts.append(PERF_PREAMBLE)
        parts.append("\n### Optimized roofline (single-pod)\n")
        parts.append(markdown_table(opt_rows, "single"))
        parts.append("\n\n### Before/after (roofline step = max of 3 terms)\n")
        parts.append(_fmt_opt_compare(
            [r for r in base_rows if r["mesh"] == "single"],
            [r for r in opt_rows if r["mesh"] == "single"]))
        parts.append("\n\n### Multi-pod before/after\n")
        parts.append(_fmt_opt_compare(
            [r for r in base_rows if r["mesh"] == "multi"],
            [r for r in opt_rows if r["mesh"] == "multi"]))

    if PERF_NARRATIVE:
        parts.append("\n\n" + PERF_NARRATIVE)

    parts.append("\n\n## §Paper-claims — DEFA figure reproductions\n")
    parts.append(CLAIMS_PREAMBLE)
    if "fig7a_bank_sim" in bench:
        r = bench["fig7a_bank_sim"]
        parts.append(
            f"\n**Fig. 7a (inter- vs intra-level parallelism)** — bank "
            f"simulator: inter-level is conflict-free by construction "
            f"({r['inter_conflict_free']}); throughput ratio "
            f"**{r['throughput_ratio']:.2f}x** (paper: 3.06x). Intra-level "
            f"averages {r['intra_cycles_per_group']:.2f} cycles per "
            f"4-point group vs {r['inter_cycles_per_group']:.2f}.\n")
    if "fig7b_energy" in bench:
        e = bench["fig7b_energy"]
        parts.append(
            f"\n**Fig. 7b (fusion + fmap reuse energy)** — byte-accounting "
            f"model: operator fusion saves {e['dram_saving_fusion_pct']:.1f}% "
            f"DRAM / {e['sram_saving_fusion_pct']:.1f}% SRAM (paper: 73.3% / "
            f"15.9%); fmap reuse saves {e['dram_saving_reuse_pct']:.1f}% DRAM "
            f"/ {e['sram_saving_reuse_pct']:.1f}% SRAM (paper: 88.2% / "
            f"22.7%). Combined: {e['total_saving_pct']:.1f}% of MSGS memory "
            f"energy. The reuse numbers match; fusion attribution differs "
            f"because the paper's unfused baseline accounting (how much of "
            f"the bounded-range fetch it charges to the fusion experiment) "
            f"is not fully specified — our model charges full range fetches, "
            f"diluting the sampled-value share.\n")
    if "fig6" in bench:
        r = bench["fig6"]
        ap = r["ap"]
        red = r["reduction"]
        parts.append("\n**Fig. 6a (AP under each mechanism)** — toy synthetic "
                     "detection (COCO unavailable offline), NO finetuning "
                     "recovery step:\n\n")
        parts.append("| variant | AP | ΔAP |\n|---|---|---|\n")
        for k, v in ap.items():
            parts.append(f"| {k} | {v:.4f} | {v - ap['baseline']:+.4f} |\n")
        parts.append(
            f"\n**Fig. 6b (reductions)** — FWP prunes "
            f"**{red['fmap_pixels_pruned_pct']:.0f}%** of fmap pixels "
            f"(paper: 43%); PAP prunes "
            f"**{red['sampling_points_pruned_pct']:.0f}%** of sampling "
            f"points at threshold 0.02 (paper: 84% — our toy detector is "
            f"2 blocks / 80 steps, so attention is far less peaked than "
            f"a converged COCO model; the FWP ratio, which depends on "
            f"sampling GEOMETRY rather than training sharpness, lands on "
            f"the paper's number); MSGS compute saved "
            f"{red['msgs_compute_saved_pct']:.0f}% (paper: >50%).\n")
    if "decoder_head" in bench:
        r = bench["decoder_head"]
        reuse = bench.get("fmap_reuse_vmem", {})
        parts.append(
            f"\n**Decoder head (shared ValueCache)** — DETR-style decoder "
            f"({r['n_layers']} layers × {r['n_queries']} learned queries) "
            f"over the encoder memory, every layer sampling ONE build-once "
            f"FWP-compactable value table: toy synthetic-task AP "
            f"**{r['ap']:.3f}** (with the full DEFA stack — PAP-topk, "
            f"FWP-compact, range-narrowing, INT12 — {r['ap_defa']:.3f}; "
            f"set-matching loss — Hungarian assignment via scipy's "
            f"linear_sum_assignment when installed, greedy per-gt argmin "
            f"fallback — so not comparable to the dense per-pixel head's "
            f"AP above). ")
        if "decoder_reuse_ratio" in reuse:
            parts.append(
                f"Staged-bytes accounting for the paper-scale 6-layer "
                f"decoder: rebuild-per-layer "
                f"{reuse['decoder_rebuild_kb']:.0f} KB vs build-once "
                f"{reuse['decoder_cache_once_kb']:.0f} KB = "
                f"**{reuse['decoder_reuse_ratio']:.1f}x** reduction — by "
                f"construction (rebuild restages the identical table per "
                f"layer); the measured evidence is the "
                f"`msda_decoder6_cached` vs `msda_decoder6_rebuild` micro "
                f"wall-time rows plus the spy-tested exactly-once "
                f"projection, and the compact build "
                f"({reuse['decoder_cache_once_kb']:.0f} KB vs dense "
                f"{reuse['decoder_cache_dense_kb']:.0f} KB) is the part "
                f"that can regress (benchmarks/fmap_reuse.py).")
        if "table_dtype_ratio" in reuse:
            parts.append(
                f" The **int8 value table** (codes + one per-channel f32 "
                f"scale row, dequantized in-register after the bilinear "
                f"corner gather) shrinks the same staged build from "
                f"{reuse['table_f32_kb']:.0f} KB (f32) to "
                f"{reuse['table_int8_kb']:.0f} KB = "
                f"**{reuse['table_dtype_ratio']:.2f}x** fewer staged bytes "
                f"— measured from the same plan accounting as the FWP "
                f"compaction ratio, and multiplicative with it "
                f"(`fmap_reuse_table_dtype` row; parity within the "
                f"analytic scale/2 tolerance is tested across all four "
                f"backends).")
        if "ordering_ratio" in reuse:
            parts.append(
                f" **Cache-local query ordering** (repro/msda/ordering.py) "
                f"permutes the decode queries by reference point before "
                f"sampling and inverts the permutation on the output — "
                f"bit-identical numerics (permutation-parity tested per "
                f"backend), but each tile of {reuse['ordering_tile_q']} "
                f"queries now spans a spatially compact set of points, so "
                f"the per-tile staging window shrinks: measured on "
                f"{reuse['ordering_queries']} uniform-random decode "
                f"queries, {reuse['ordering_unordered_kb']:.0f} KB/tile "
                f"unordered vs {reuse['ordering_raster_kb']:.0f} KB "
                f"raster-ordered = **{reuse['ordering_ratio']:.2f}x** "
                f"smaller mean window (z-order: "
                f"{reuse['ordering_zorder_kb']:.0f} KB, "
                f"{reuse['ordering_zorder_ratio']:.2f}x — row-span-based "
                f"staging credits raster's row locality, not z-order's "
                f"column locality). `plan.describe()` reports the same "
                f"measured figure (`tilewin=`), and the `auto` policy can "
                f"use it for the VMEM-fit check; wall-time rows: "
                f"`msda_decode6_ordered`, `msda_windowed_ordered`.")
        micro = bench.get("micro", {})
        if "msda_decoder6_persistent" in micro \
                and "msda_decoder6_cached" in micro:
            pers = micro["msda_decoder6_persistent"]["us_per_call"]
            cach = micro["msda_decoder6_cached"]["us_per_call"]
            parts.append(
                f" The **persistent decode kernel** (`pallas_decode`, "
                f"kernels/msgs_decode.py) extends build-once from "
                f"projection to staging: the compact table is laid out in "
                f"the launch layout ONCE per memory (spy-tested once per "
                f"(batch, head-group), never per layer) and every layer's "
                f"launch reuses it — 6-layer cross-attn stack "
                f"{pers/1000:.1f} ms vs the `jnp_gather` cached baseline "
                f"{cach/1000:.1f} ms (**{cach/pers:.1f}x**, "
                f"`msda_decoder6_persistent` vs `msda_decoder6_cached`, "
                f"interpret-mode structural wall time under the CI "
                f"regression gate).")
            if "msda_decode6_stacked_launch" in micro \
                    and "msda_decode6_perlayer_launches" in micro:
                st_us = micro["msda_decode6_stacked_launch"]["us_per_call"]
                pl_us = micro["msda_decode6_perlayer_launches"]["us_per_call"]
                parts.append(
                    f" On identical precomputed points, the stacked "
                    f"single-launch variant (layer axis innermost, table "
                    f"resident per (batch, head-group)) runs 6 layers in "
                    f"{st_us/1000:.1f} ms vs {pl_us/1000:.1f} ms for 6 "
                    f"per-layer launches — interpret mode can't show the "
                    f"per-launch DMA saving, so the stacked win is "
                    f"structural (one table fetch per (b, group)), not "
                    f"wall-time.")
        parts.append("\n")
    reuse = bench.get("fmap_reuse_vmem", {})
    micro = bench.get("micro", {})
    if "stream_bytes_ratio" in reuse:
        r = reuse
        parts.append(
            f"\n**Streaming detection (temporal feature-map reuse)** — the "
            f"frame-to-frame extension of the build-once story: a "
            f"`TemporalCacheManager` (repro/stream/) diffs each video "
            f"frame's multi-scale memory at row-aligned tile granularity "
            f"and re-projects/re-stages ONLY the dirty slots of the "
            f"persistent value cache (scattered through the existing "
            f"pix2slot geometry), with FWP scores carried as a streaming "
            f"EMA under keep-mask hysteresis. On the measured "
            f"{r['stream_frames']}-frame drifting-scene benchmark: "
            f"rebuild-per-frame {r['stream_rebuild_total_kb']:.0f} KB vs "
            f"incremental {r['stream_staged_total_kb']:.0f} KB staged = "
            f"**{r['stream_bytes_ratio']:.2f}x fewer bytes** "
            f"({r['stream_incremental_frames']}/{r['stream_frames']} frames "
            f"incremental at <= {r['stream_update_rows']}/"
            f"{r['stream_slots']} rows/frame; "
            f"{r['stream_rebuild_frames']} full rebuilds incl. the warm-up "
            f"keep transitions the hysteresis then suppresses). This is a "
            f"measurement — how many tiles the moving object dirties and "
            f"how often the keep set churns decide it — not a "
            f"by-construction ratio.")
        if "msda_stream_incremental" in micro \
                and "msda_stream_rebuild" in micro:
            i_us = micro["msda_stream_incremental"]["us_per_call"]
            b_us = micro["msda_stream_rebuild"]["us_per_call"]
            parts.append(
                f" Wall time per frame (d_model=256, 32x40 pyramid, "
                f"interpret-mode structural): incremental "
                f"{i_us/1000:.1f} ms vs full rebuild {b_us/1000:.1f} ms "
                f"(`msda_stream_incremental` vs `msda_stream_rebuild`, "
                f"both under the CI regression gate); at the paper's "
                f"100x167 geometry the measured gap widens to ~2x but is "
                f"too noisy for the gate. End-to-end driver: "
                f"`examples/detr_stream.py` (N sessions, batched slots, "
                f"decoder-frequency EMA feedback).")
        parts.append("\n")
    auto_par = _autotune_paragraph(bench)
    if auto_par:
        parts.append(auto_par)
    serve = bench.get("serve_sustained", {})
    if "closed_loop" in serve:
        cl, ol = serve["closed_loop"], serve["open_loop"]
        w = serve.get("workload", {})
        buckets = ", ".join(
            f"{b['resolution']}px ({b['table_kb']} KB table)"
            for b in serve.get("buckets", []))
        parts.append(
            f"\n**Sustained serving (AOT shape buckets + continuous "
            f"batching + pipelined post-processing)** — the deployment "
            f"harness (repro/serve/): each resolution bucket's detector "
            f"forward is AOT-compiled at startup "
            f"(`jax.jit(...).lower().compile()`; buckets: {buckets}), "
            f"requests route to the smallest bucket they fit (pad up, "
            f"reject oversized), micro-batches dispatch from per-bucket "
            f"queues, and top-k decode + callbacks run on a worker thread "
            f"while the device serves the next batch. On the "
            f"{w.get('mix', 'mixed')} mixed-resolution load "
            f"(closed loop, median of 3): "
            f"{cl['sustained_us_per_request']/1000:.1f} ms/request vs "
            f"{cl['single_bucket_sync_us_per_request']/1000:.1f} ms/request "
            f"for the single-bucket synchronous baseline = "
            f"**{cl['speedup']:.2f}x sustained throughput** "
            f"(`msda_serve_sustained` vs `msda_serve_single_bucket_sync`, "
            f"both under the CI regression gate), with ZERO recompiles "
            f"after warmup (compile-count spy, tests/test_serve.py). Open "
            f"loop at 0.9x measured capacity: "
            f"{ol['rps_per_chip']} requests/s/chip, P50 {ol['p50_ms']} ms "
            f"/ P99 {ol['p99_ms']} ms request latency (submit -> "
            f"post-processing done). Driver: `examples/detr_serve.py "
            f"--sustained`.\n")
    if "spans" in serve or "observability" in serve:
        spans = serve.get("spans", {})
        span_tbl = "; ".join(
            f"`{name}` P50 {st['p50_ms']:.2f} ms / P99 {st['p99_ms']:.2f} ms "
            f"(n={st['count']})"
            for name, st in sorted(spans.items())
            if name in ("queue", "serve.dispatch", "serve.fetch",
                        "postproc", "callback"))
        obs = serve.get("observability", {})
        parts.append(
            f"\n**Observability (repro/obs/)** — the same run, decomposed by "
            f"the request-tracing spans the engine emits "
            f"(`enqueue -> admit -> device_step -> postproc`): {span_tbl}. "
            f"Every engine owns a `MetricsRegistry` + `Tracer` bundle; the "
            f"zero-retrace contract is asserted against the "
            f"`msda_compiles_total` counter (bumped at trace time, flat "
            f"after warmup), and the Prometheus/JSONL exports are "
            f"CI-validated (`python -m repro.obs.validate`). Measured "
            f"instrumentation cost: "
            f"{obs.get('instrumentation_us_per_request', 0):.1f} us/request "
            f"= **{100 * obs.get('fraction_of_request', 0):.2f}%** of a "
            f"request (<1% acceptance bar; plain-dict counters outside "
            f"jit). Live view: `python -m repro.obs.dashboard --jsonl "
            f"$REPRO_OBS_JSONL --follow`.\n")
    if "fig9_table1" in bench and "baseline" in bench.get("fig9_table1", {}):
        r = bench["fig9_table1"]
        parts.append(
            f"\n**Fig. 9 / Table 1 analogue** — TPU-v5e roofline of the DETR "
            f"encoder serve cell: plain encoder "
            f"{r['baseline']['roofline_step_ms']:.2f} ms/step; naive DEFA "
            f"{r['defa']['roofline_step_ms']:.2f} ms/step (the pruning "
            f"machinery is collective-bound when only the batch axis is "
            f"used — an honest negative result the paper's ASIC never "
            f"faces)")
        if "defa_banded" in r:
            parts.append(
                f"; DEFA + band-sharded halo exchange "
                f"{r['defa_banded']['roofline_step_ms']:.2f} ms/step = "
                f"**{r.get('defa_banded_vs_baseline_speedup', 0):.2f}x over "
                f"the plain encoder** and "
                f"{r['defa']['roofline_step_ms']/r['defa_banded']['roofline_step_ms']:.1f}x "
                f"over naive DEFA ("
                f"{r['defa_banded']['imgs_per_s_per_chip']:.1f} img/s/chip)")
        parts.append(
            f". The paper's 10.1-31.9x is vs a CUDA grid-sample baseline on "
            f"GPUs — not comparable 1:1. Energy: the byte-accounting model "
            f"gives {r['energy_model']['msgs_energy_saving_pct']:.1f}% MSGS "
            f"memory-energy saving (fusion+reuse), vs the paper's "
            f"20.3-37.7x GPU energy-efficiency claim driven by the same "
            f"mechanisms.\n")

    with open("EXPERIMENTS.md", "w") as f:
        f.write("".join(parts))
    print("wrote EXPERIMENTS.md",
          f"({len(base_rows)} baseline cells, {len(opt_rows)} optimized)")


def _autotune_paragraph(bench: dict) -> str:
    """Measured-vs-static budget story from results/autotune.json: the
    per-platform calibration winners, plus the concrete plan delta the
    measured budget buys on the paper 4-level shape."""
    path = "results/autotune.json"
    if not os.path.exists(path):
        return ""
    with open(path) as f:
        table = json.load(f)
    plats = table.get("platforms", {})
    if not plats:
        return ""
    out = ["\n**Plan autotuning (measured vs static budgets)** — "
           "`repro/msda/autotune.py` replaces three static planner guesses "
           "with on-device measurements, persisted per platform in "
           "`results/autotune.json` (committed fallback for CI; "
           "`plan.describe()` reports the provenance as "
           "`budget=measured|static`):\n"]
    for plat, e in sorted(plats.items()):
        mb = e.get("staging_budget_bytes", 0) / 2**20
        stream = e.get("stream", {})
        out.append(
            f"\n- `{plat}`: staged-table budget **{mb:.0f} MB measured** "
            f"(bandwidth-knee probe) vs the 4 MB static default "
            f"({mb / 4:.0f}x); persistent decode sweep "
            f"{'KEPT' if e.get('decode_sweep_beneficial') else 'VETOED'} "
            f"(measured {e.get('decode_persistent_speedup', 0):.2f}x vs "
            f"per-layer restaging, interpret-mode); streaming crossover "
            f"`diff_channel_stride={stream.get('diff_channel_stride')}` / "
            f"`update_frac={stream.get('update_frac')}`.\n")
    delta = _paper_shape_budget_delta(plats)
    if delta:
        out.append(delta)
    micro = bench.get("micro", {})
    if "msda_autotune_load_plan" in micro:
        us = micro["msda_autotune_load_plan"]["us_per_call"]
        out.append(
            f"\nStartup cost after the one-off calibration run: loading + "
            f"applying the table and resolving an un-memoized auto plan "
            f"measures {us / 1000:.1f} ms (`msda_autotune_load_plan`, under "
            f"the CI regression gate); engines pay it once at "
            f"construction via the load-only `msda.ensure_applied()`.\n")
    return "".join(out)


def _paper_shape_budget_delta(plats: dict) -> str:
    """The measured budget's consequence on the paper 4-level pyramid —
    best-effort (the doc generator must not die on an import problem)."""
    try:
        import jax

        from repro.core.msdeform_attn import MSDeformAttnConfig
        from repro.msda import plan as plan_lib

        entry = plats.get(jax.default_backend())
        if not entry:
            return ""
        paper_levels = ((100, 167), (50, 84), (25, 42), (13, 21))
        cfg = MSDeformAttnConfig(d_model=256, n_heads=8,
                                 range_narrow=(8.0, 6.0, 4.0, 3.0))
        prev = plan_lib.tuned_entry()
        try:
            plan_lib.apply_tuned_plan_table(None)
            p_stat = plan_lib.make_plan(cfg, paper_levels, backend="auto",
                                        n_queries=300, n_consumers=6)
            plan_lib.apply_tuned_plan_table(entry)
            p_meas = plan_lib.make_plan(cfg, paper_levels, backend="auto",
                                        n_queries=300, n_consumers=6)
        finally:
            plan_lib.apply_tuned_plan_table(prev)
        staged_kb = p_meas.cache_table_bytes / 1024
        meas_mb = p_meas.staging_budget_bytes // 2**20
        stat_mb = plan_lib.DEFAULT_WINDOW_STAGING_BUDGET // 2**20
        vmem_mb = p_meas.vmem_budget_bytes / 2**20
        if p_stat.backend != p_meas.backend:
            story = (
                f"flips the auto decode plan from `{p_stat.backend}` to "
                f"`{p_meas.backend}`: the {staged_kb:.0f} KB staged decode "
                f"table clears the measured {meas_mb} MB ceiling but not "
                f"the static {stat_mb} MB guess")
        elif staged_kb * 1024 <= p_meas.staging_budget_bytes:
            # the table fits the measured staging ceiling, so the staging
            # budget is not what keeps the backend — the kernel VMEM
            # budget binds first at this shape
            story = (
                f"keeps `{p_meas.backend}`: the {staged_kb:.0f} KB staged "
                f"decode table clears the measured {meas_mb} MB staging "
                f"ceiling (it missed the static {stat_mb} MB guess), but "
                f"the {vmem_mb:.0f} MB kernel VMEM budget still binds "
                f"first at this shape")
        else:
            story = (
                f"keeps `{p_meas.backend}`: the {staged_kb:.0f} KB staged "
                f"decode table exceeds even the measured {meas_mb} MB "
                f"ceiling")
        return (
            f"\nOn the paper 4-level shape (100x167 pyramid, d_model=256, "
            f"300 decode queries, 6 layers) the measured budget {story} — "
            f"every later kernel improvement lands in production through "
            f"the same measured gate instead of waiting for a hand-raised "
            f"constant.\n")
    except Exception:                       # noqa: BLE001 - doc generator
        return ""


HEADER = """# EXPERIMENTS — DEFA on TPU

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
Container is CPU-only: kernels validate in interpret mode; distribution
validates by AOT compile on 512 virtual devices; roofline terms derive from
compiled HLO (see DESIGN.md §7 and launch/hlo_stats.py for conventions,
including the two-point scan-cost correction and the structural HBM-bytes
estimate).
"""

DRYRUN_PREAMBLE = """Every (architecture × shape) cell lowers AND compiles with
explicit in/out shardings + donated state/caches on the production meshes
(`launch/dryrun.py`). `long_500k` runs for mamba2-130m and hymba-1.5b
(sub-quadratic); the eight pure full-attention archs skip it per the
assignment (DESIGN.md §5). whisper/llava frontends are ShapeDtypeStruct
stubs. 32 LM cells + DETR-family cells per mesh."""

ROOFLINE_PREAMBLE = """Terms per chip: compute = HLO_FLOPs/197e12, memory =
structural_bytes/819e9, collective = ring-weighted collective bytes/50e9.
`useful` = MODEL_FLOPS(6·N·D train, 2·N·D serve)/HLO_FLOPs; `MFU` =
useful-compute time / roofline step time. Full per-cell JSON (incl.
collective op histograms) in results/dryrun*/.
"""

PERF_PREAMBLE = """Optimized = `--opt`: O1 activation-sharding constraints,
O2 seq-parallel/padded attention for TP-indivisible heads, O3 SSD projection
split, O4' explicit shard_map expert parallelism, O5 grad-accum memory
fitting, O6 save_comm remat, O7 pure-DP strategy for small archs. The
hypothesis→measure log for each is in §Perf iterations below."""

CLAIMS_PREAMBLE = """Each paper figure/table has a benchmark
(`python -m benchmarks.run`); numbers below are from the latest run
(results/benchmarks.json)."""


if __name__ == "__main__":
    main()
