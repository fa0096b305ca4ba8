"""Streaming-video detection driver: temporal value-cache reuse.

N concurrent synthetic video sessions stream drifting-scene encoder
memories through the :class:`~repro.serve.engine.StreamingDetrEngine`:
each session holds a PERSISTENT, incrementally updated
``MSDAValueCache`` — per frame only the tiles the moving object dirtied
are re-projected and re-staged (scattered through the existing pix2slot
geometry), the FWP keep decision rides a streaming EMA with keep-mask
hysteresis, and the decoder + heads run one batched jitted forward
against the shared cache.

  PYTHONPATH=src python examples/detr_stream.py --frames 4 --dry-run
  PYTHONPATH=src python examples/detr_stream.py --frames 32 --sessions 2
"""
import argparse
import time

import jax
import numpy as np

from repro import msda
from repro.core import nn
from repro.core.msdeform_attn import MSDeformAttnConfig, init_msdeform_attn
from repro.serve.engine import StreamingDetrEngine
from repro.stream import StreamConfig, drifting_scene
from repro.utils.compile_cache import enable_compile_cache

DRY_LEVELS = ((16, 20), (8, 10), (4, 5), (2, 3))
FULL_LEVELS = ((32, 40), (16, 20), (8, 10), (4, 5))


def build_engine(args):
    levels = DRY_LEVELS if args.dry_run else FULL_LEVELS
    d = 64 if args.dry_run else 128
    attn_cfg = MSDeformAttnConfig(
        d_model=d, n_heads=4, fwp_mode="compact", fwp_k=1.0,
        fwp_capacity=0.6, range_narrow=(8.0, 6.0, 4.0, 3.0))
    dec_cfg = msda.MSDADecoderConfig(
        n_layers=3 if args.dry_run else 6,
        n_queries=32 if args.dry_run else 100,
        d_ffn=2 * d)
    key = jax.random.PRNGKey(7)
    params = {
        "decoder": msda.init_decoder(key, dec_cfg, attn_cfg),
        "cls_head": nn.linear_init(jax.random.fold_in(key, 1), d, 5),
        "box_head": nn.linear_init(jax.random.fold_in(key, 2), d, 4),
    }
    scfg = StreamConfig(tile_rows=args.tile_rows,
                        delta_threshold=args.threshold,
                        update_frac=args.update_frac,
                        diff_channel_stride=args.diff_stride)
    engine = StreamingDetrEngine(attn_cfg, dec_cfg, params, levels,
                                 max_sessions=args.sessions,
                                 backend=args.backend, stream_cfg=scfg)
    return engine, levels, d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--sessions", type=int, default=2)
    ap.add_argument("--backend", default=None,
                    choices=msda.available_backends() + ["auto"])
    ap.add_argument("--tile-rows", type=int, default=1)
    ap.add_argument("--threshold", type=float, default=1e-4)
    ap.add_argument("--update-frac", type=float, default=0.3)
    ap.add_argument("--diff-stride", type=int, default=4,
                    help="probe every s-th feature channel when diffing "
                         "tiles (1 = exact)")
    ap.add_argument("--churn", action="store_true",
                    help="mid-stream session churn: one session leaves and "
                         "a new one joins halfway — its slot is rebuilt "
                         "from its own first frame (per-slot admission) "
                         "while the others stay incremental")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shapes / few layers (the CI smoke path)")
    args = ap.parse_args()
    enable_compile_cache()

    engine, levels, d = build_engine(args)
    print(f"[stream] {engine.describe()}")
    if args.dry_run:
        # sessions-per-chip at the default 4 MB staging budget: the
        # serving consequence of the int8 table (one line per dtype)
        cap = engine.capacity_estimate()
        print(f"[stream] capacity @ {cap['budget_bytes'] // 1024} KB budget "
              f"({cap['rows_per_session']} rows/session, "
              f"active dtype {cap['table_dtype']}):")
        for dt_name, row in cap["per_dtype"].items():
            print(f"[stream]   {dt_name:8s} "
                  f"{row['bytes_per_session'] / 1024:7.1f} KB/session -> "
                  f"{row['sessions']} sessions/chip")

    sids = [engine.open_session() for _ in range(args.sessions)]
    scenes = {sid: drifting_scene(100 + i, levels, d, args.frames,
                                  obj_rows=1, speed_rows=1)
              for i, sid in enumerate(sids)}
    # warm compile: first frame of every session (a rebuild frame anyway)
    for sid in sids:
        engine.submit_frame(sid, scenes[sid][0][0])
    engine.step()

    churn_at = args.frames // 2 \
        if args.churn and args.sessions > 1 and args.frames > 2 else None
    left = []
    t0 = time.perf_counter()
    for t in range(1, args.frames):
        if t == churn_at:
            old = sids.pop()
            left.append(engine.close_session(old))
            new = engine.open_session()
            sids.append(new)
            scenes[new] = drifting_scene(200 + new, levels, d, args.frames,
                                         obj_rows=1, speed_rows=1)
            print(f"[stream] churn: session {old} left after "
                  f"{left[-1].frames_done} frames, session {new} joined — "
                  "per-slot admission, neighbours stay incremental")
        for sid in sids:
            engine.submit_frame(sid, scenes[sid][t][0])
        engine.step()
        st = engine.mgr.last_stats
        print(f"frame {t}: {st['mode']:11s} "
              f"staged {st['staged_bytes']/1024:6.1f} KB "
              f"(rebuild would stage {st['rebuild_bytes']/1024:6.1f} KB), "
              f"dirty slots {st['n_dirty']}/{st['update_rows']}, "
              f"tiles {st['tiles_changed']}"
              + (f" [{st['reason']}]" if st["reason"] else "")
              + (f" [admitted slots {st['admitted_slots']}]"
                 if st.get("admitted_slots") else ""))
    dt = time.perf_counter() - t0

    r = engine.report()
    served = (args.frames - 1) * args.sessions
    print(f"\n[stream] {args.frames} frames x {args.sessions} sessions: "
          f"{served} timed frames in {dt:.2f}s = "
          f"{served/max(dt, 1e-9):.2f} frames/s "
          f"({jax.devices()[0].platform} {jax.devices()[0].device_kind})")
    print(f"[stream] staged bytes: rebuild-per-frame "
          f"{r['rebuild_bytes_total']/1024:.0f} KB vs incremental "
          f"{r['staged_bytes_total']/1024:.0f} KB = "
          f"{r['bytes_ratio']:.2f}x fewer "
          f"({r['incremental_frames']}/{r['frames']} frames incremental, "
          f"update cap {r['update_rows']}/{r['n_slots']} rows)")
    for sid in sids:
        sess = engine.close_session(sid)
        boxes = np.stack([f["boxes"] for f in sess.results])
        print(f"[stream] session {sid}: {len(sess.results)} frames, "
              f"mean box {np.mean(boxes, axis=(0, 1)).round(3)}")


if __name__ == "__main__":
    main()
