"""plan_autotune — the measured plan table behind ``backend="auto"``.

Three planner inputs were guesses until this module: the 4 MB
``DEFAULT_WINDOW_STAGING_BUDGET`` for windowed/decode staging fit, the
assumption that the ``pallas_decode`` (query-tile x layer) sweep spares
the HBM->VMEM table refetch, and the streaming diff-vs-reprojection
crossover that sets ``StreamConfig.diff_channel_stride``/``update_frac``.
:func:`plan_autotune` replaces all three with ON-DEVICE timing:

  (a) **staging budget** — a bandwidth-knee probe: a jitted
      gather+reduce over value tables of increasing size; per-byte cost
      is flat while the working set stays resident in the fast tier and
      knees upward once it spills. The measured ceiling is the largest
      probed size still within ``KNEE_TOL`` of the best per-byte cost.
  (b) **decode sweep** — an N-layer decode-shaped cross-attention stack
      through ``pallas_decode`` (table staged once per memory) vs the
      per-layer ``pallas_fused`` restage on the same cache; the verdict
      (``decode_sweep_beneficial``) vetoes the auto policy's decode gate
      on platforms where the sweep does NOT pay.
  (c) **streaming crossover** — per-frame diff cost at channel strides
      vs the re-projection cost at update fractions, against the full
      per-frame rebuild both amortize: the chosen (stride, frac) is the
      cheapest probed diff that stays a small fraction of the rebuild,
      paired with the LARGEST update budget whose incremental frame
      still clearly undercuts rebuilding.

Winners persist in a per-platform JSON table (``results/autotune.json``,
keyed by ``jax.default_backend()`` the way ``results/benchmarks.json``
keys its sections) so measurement runs once per machine; CI and
device-less machines ride the COMMITTED table (``--no-measure``). A
corrupted/partial table falls back to the static formulas with a warning
— never a crash. The applied entry lives in :mod:`repro.msda.plan`
(``apply_tuned_plan_table``), where ``window_staging_budget()``,
``make_plan``'s auto gates, ``resolve_stream_config`` and the serve
engines consult it: ``backend="auto"`` then means "measured best".
Tuning changes WHICH backend/budget is chosen, never numerics — the
``--check`` CLI asserts tuned-vs-static bit-identity.

CLI::

    PYTHONPATH=src python -m repro.msda.autotune            # measure+persist
    PYTHONPATH=src python -m repro.msda.autotune --force    # re-tune
    PYTHONPATH=src python -m repro.msda.autotune --no-measure --check   # CI
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.msda import plan as plan_lib

SCHEMA_VERSION = 1

#: paper-style 4-level pyramid at the dry-run scale every calibration
#: measurement runs on — small enough for interpret-mode Pallas, same
#: family as the ``msda_*`` microbench rows.
CALIB_LEVELS: Tuple[Tuple[int, int], ...] = ((16, 20), (8, 10), (4, 5),
                                             (2, 3))

#: per-byte cost within this factor of the best probed size still counts
#: as "fits the fast tier" for the budget knee.
KNEE_TOL = 1.5

#: the measured budget is clamped to this sane range — a noisy probe must
#: never produce a degenerate (or absurd) ceiling.
BUDGET_CLAMP = (1 * 2**20, 64 * 2**20)

#: streaming crossover thresholds: the diff must cost at most
#: DIFF_FRAC of a full rebuild (else probe fewer channels), and an
#: incremental frame (diff + budgeted re-projection) must stay under
#: CROSSOVER_FRAC of the rebuild to justify its budget.
DIFF_FRAC = 0.25
CROSSOVER_FRAC = 0.6

#: the (32x40, d_model=256) shape the streaming crossover measures at —
#: the same geometry as the ``msda_stream_*`` microbench rows. The toy
#: CALIB_LEVELS shape is useless here: its rebuild matmul is so small
#: that fixed dispatch overheads dominate every probe and the crossover
#: degenerates to "coarsest stride, smallest budget".
STREAM_CALIB_LEVELS: Tuple[Tuple[int, int], ...] = ((32, 40), (16, 20),
                                                    (8, 10), (4, 5))
STREAM_CALIB_D_MODEL = 256

#: decode-sweep veto threshold: the sweep's real benefit is the spared
#: per-layer HBM->VMEM table refetch, which interpret-mode wall time
#: cannot observe — so the verdict only turns negative on a DECISIVE
#: measured loss (the sweep slower than per-layer restaging by more than
#: this factor), not on noise-level parity.
DECODE_VETO_TOL = 0.85


def default_table_path() -> str:
    """``results/autotune.json`` at the repo root (next to
    ``results/benchmarks.json``), overridable via the
    ``REPRO_MSDA_AUTOTUNE_TABLE`` env var."""
    env = os.environ.get("REPRO_MSDA_AUTOTUNE_TABLE")
    if env:
        return env
    here = os.path.abspath(__file__)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(here))))
    return os.path.join(root, "results", "autotune.json")


def platform_key() -> str:
    """The table's platform key — ``jax.default_backend()`` ("cpu" |
    "gpu" | "tpu"), the same axis ``results/benchmarks.json`` rows are
    implicitly scaled along."""
    return jax.default_backend()


def _default_cfg():
    from repro.core.msdeform_attn import MSDeformAttnConfig
    return MSDeformAttnConfig(d_model=64, n_heads=4,
                              range_narrow=(6.0, 4.0, 3.0, 2.0))


# --------------------------------------------------------------------------
# Table persistence
# --------------------------------------------------------------------------

def load_table(path: Optional[str] = None) -> Optional[dict]:
    """Read the persistent plan table; a missing file returns None
    silently, a corrupted/mis-shaped one returns None WITH a warning —
    the caller falls back to the static formulas, never crashes."""
    path = path or default_table_path()
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            table = json.load(f)
    except (json.JSONDecodeError, OSError, UnicodeDecodeError) as e:
        warnings.warn(f"autotune table {path!r} is unreadable ({e}); "
                      "falling back to static plan formulas",
                      RuntimeWarning, stacklevel=2)
        return None
    if not isinstance(table, dict) \
            or table.get("schema") != SCHEMA_VERSION \
            or not isinstance(table.get("platforms"), dict):
        warnings.warn(
            f"autotune table {path!r} has an unexpected shape/schema "
            f"(want schema={SCHEMA_VERSION} with a 'platforms' dict); "
            "falling back to static plan formulas",
            RuntimeWarning, stacklevel=2)
        return None
    return table


def valid_entry(entry) -> bool:
    """Structural validation of one platform entry — a PARTIAL entry (a
    truncated write, a hand-edit gone wrong) must fail closed to the
    static formulas."""
    return (isinstance(entry, dict)
            and isinstance(entry.get("staging_budget_bytes"), int)
            and entry["staging_budget_bytes"] > 0
            and isinstance(entry.get("decode_sweep_beneficial"), bool)
            and isinstance(entry.get("stream"), dict)
            and isinstance(entry["stream"].get("diff_channel_stride"), int)
            and entry["stream"]["diff_channel_stride"] >= 1
            and isinstance(entry["stream"].get("update_frac"), (int, float))
            and 0.0 < float(entry["stream"]["update_frac"]) <= 1.0)


def save_entry(entry: dict, path: Optional[str] = None,
               platform: Optional[str] = None) -> str:
    """Merge one platform's entry into the table on disk (other
    platforms' rows survive — the committed table carries every machine
    the suite has run on, like ``results/benchmarks.json``)."""
    path = path or default_table_path()
    platform = platform or platform_key()
    table = load_table(path) or {"schema": SCHEMA_VERSION, "platforms": {}}
    table["platforms"][platform] = entry
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


# --------------------------------------------------------------------------
# Timing primitives
# --------------------------------------------------------------------------

def _time(fn, *args, iters: int = 5) -> float:
    """Median wall seconds per call (warm; block_until_ready) — the same
    discipline as benchmarks/microbench.py, fewer iters: startup
    calibration must stay cheap."""
    fn(*args)
    ts = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def measure_staging_budget(sizes_mb: Sequence[int] = (1, 2, 4, 8, 16, 32),
                           iters: int = 5) -> Tuple[int, dict]:
    """(a) The staged-table VMEM/fast-tier ceiling, by bandwidth knee.

    Times a jitted sweep+gather over a (rows, 128) f32 table per probed
    size; the per-byte cost curve is flat while the table stays resident
    and knees upward at the spill point. Returns (budget_bytes, detail):
    the largest probed size within ``KNEE_TOL`` of the best per-byte
    cost, clamped to ``BUDGET_CLAMP``."""
    lanes = 128
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 4096, size=4096), jnp.int32)

    @jax.jit
    def probe(v, i):
        # one full pass over the table (the staging fetch) + a bounded
        # gather (the sampling access pattern riding on the staged copy)
        return v.sum() + jnp.take(v, i, axis=0).sum()

    per_byte = {}
    for mb in sizes_mb:
        rows = max(4096, (int(mb) * 2**20) // (lanes * 4))
        v = jnp.asarray(rng.standard_normal((rows, lanes)), jnp.float32)
        nbytes = rows * lanes * 4
        per_byte[int(mb)] = _time(probe, v, idx, iters=iters) / nbytes
    best = min(per_byte.values())
    fitting = [mb for mb in per_byte if per_byte[mb] <= KNEE_TOL * best]
    budget = max(fitting) * 2**20
    budget = int(min(max(budget, BUDGET_CLAMP[0]), BUDGET_CLAMP[1]))
    detail = {"sizes_mb": [int(m) for m in sizes_mb],
              "ns_per_byte": {str(m): per_byte[m] * 1e9 for m in per_byte},
              "knee_tol": KNEE_TOL, "budget_bytes": budget}
    return budget, detail


def measure_decode_sweep(cfg=None,
                         level_shapes: Optional[Sequence] = None,
                         n_layers: int = 3, iters: int = 3,
                         repeats: int = 3) -> Tuple[bool, float, dict]:
    """(b) Does the persistent decode sweep spare the table refetch HERE?

    Times an ``n_layers`` decode-shaped cross-attention stack sampling
    ONE built cache through ``pallas_decode`` (table staged once per
    memory, every layer's launch reuses it) vs ``pallas_fused`` (each
    layer's launch restages the whole table). The calibration stack is
    tiny enough to be scheduler-noise dominated, and noise only ever
    inflates a timing — so each backend's cost is the MIN over
    ``repeats`` interleaved timing rounds. Returns
    (beneficial, speedup, detail) with speedup = fused_t / decode_t;
    beneficial is ``speedup >= DECODE_VETO_TOL`` — only a decisive
    measured loss vetoes the sweep, since the refetch saving itself is
    invisible to interpret-mode wall time."""
    from repro import msda

    cfg = cfg or _default_cfg()
    level_shapes = tuple(tuple(s) for s in (level_shapes or CALIB_LEVELS))
    from repro.core.msdeform_attn import init_msdeform_attn
    key = jax.random.PRNGKey(11)
    params = init_msdeform_attn(key, cfg)
    nq = 64
    n_in = sum(h * w for h, w in level_shapes)
    memory = jax.random.normal(jax.random.fold_in(key, 1),
                               (1, n_in, cfg.d_model))
    q = jax.random.normal(jax.random.fold_in(key, 2), (1, nq, cfg.d_model))
    refs = jax.random.uniform(jax.random.fold_in(key, 3), (1, nq, 2),
                              minval=0.1, maxval=0.9)
    vparams = {k: params[k] for k in ("value_w", "value_b")}

    from repro.msda.backends import candidate_backends
    names = [n for n in candidate_backends(decode_shaped=True)
             if n in ("pallas_decode", "pallas_fused")]
    assert names == ["pallas_decode", "pallas_fused"], names

    fns = {}
    for name in names:
        plan = msda.make_plan(cfg, level_shapes, backend=name, n_queries=nq,
                              n_consumers=n_layers)

        def stack(p_, m_, q_, r_, plan=plan):
            cache = msda.build_value_cache(vparams, plan, m_)
            out = q_
            for _ in range(n_layers):
                o, _st = msda.msda_attention_cached(p_, plan, out, r_,
                                                    cache, update_fwp=False)
                out = out + o
            return out

        fns[name] = jax.jit(stack)
    times = {name: float("inf") for name in names}
    for _ in range(max(1, repeats)):
        for name in names:
            t = _time(fns[name], params, memory, q, refs, iters=iters)
            times[name] = min(times[name], t)
    speedup = times["pallas_fused"] / max(times["pallas_decode"], 1e-12)
    detail = {"n_layers": n_layers, "n_queries": nq,
              "level_shapes": [list(s) for s in level_shapes],
              "decode_s": times["pallas_decode"],
              "fused_s": times["pallas_fused"], "speedup": speedup,
              "repeats": max(1, repeats), "veto_tol": DECODE_VETO_TOL}
    return bool(speedup >= DECODE_VETO_TOL), float(speedup), detail


def measure_stream_crossover(d_model: int = STREAM_CALIB_D_MODEL,
                             level_shapes: Optional[Sequence] = None,
                             strides: Sequence[int] = (1, 2, 4),
                             fracs: Sequence[float] = (0.5, 0.25, 0.125),
                             tile_rows: int = 2, iters: int = 5
                             ) -> Tuple[int, float, dict]:
    """(c) The streaming diff-vs-reprojection crossover.

    Measures, on a synthetic memory at the calibration shape: the
    tile-diff cost per probed ``diff_channel_stride``, the budgeted
    re-projection cost per ``update_frac`` (a (B, U, D) projection — the
    incremental path's proportional term), and the full per-frame
    rebuild both amortize. Picks the smallest stride whose diff stays
    under ``DIFF_FRAC`` of the rebuild (exact diffing is preferred —
    larger strides only delay sub-probe changes), then the LARGEST frac
    whose incremental frame (diff + update) undercuts
    ``CROSSOVER_FRAC`` x rebuild. Returns (stride, frac, detail)."""
    from repro.stream.tiles import changed_tiles, tile_geometry

    level_shapes = tuple(tuple(s)
                         for s in (level_shapes or STREAM_CALIB_LEVELS))
    n_in = sum(h * w for h, w in level_shapes)
    geo = tile_geometry(level_shapes, tile_rows)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, n_in, d_model)), jnp.float32)
    ref = x + jnp.asarray(
        1e-3 * rng.standard_normal((1, n_in, d_model)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d_model, d_model)) / np.sqrt(d_model),
                    jnp.float32)

    diff_t = {}
    for s in strides:
        fn = jax.jit(lambda a, b, s=int(s):
                     changed_tiles(geo, a[..., ::s], b[..., ::s], 1e-5))
        diff_t[int(s)] = _time(fn, x, ref, iters=iters)

    rebuild = jax.jit(lambda a, w_: a @ w_)
    rebuild_t = _time(rebuild, x, w, iters=iters)

    update_t = {}
    for f in fracs:
        u = max(1, int(round(float(f) * n_in)))
        proj = jax.jit(lambda a, w_, u=u: a[:, :u] @ w_)
        update_t[float(f)] = _time(proj, x, w, iters=iters)

    stride = max(int(s) for s in strides)
    for s in sorted(int(s) for s in strides):
        if diff_t[s] <= DIFF_FRAC * rebuild_t:
            stride = s
            break
    frac = min(float(f) for f in fracs)
    for f in sorted((float(f) for f in fracs), reverse=True):
        if diff_t[stride] + update_t[f] <= CROSSOVER_FRAC * rebuild_t:
            frac = f
            break
    detail = {"level_shapes": [list(s) for s in level_shapes],
              "d_model": d_model, "tile_rows": tile_rows,
              "diff_s": {str(k): v for k, v in diff_t.items()},
              "update_s": {str(k): v for k, v in update_t.items()},
              "rebuild_s": rebuild_t, "diff_frac": DIFF_FRAC,
              "crossover_frac": CROSSOVER_FRAC,
              "diff_channel_stride": stride, "update_frac": frac}
    return stride, frac, detail


# --------------------------------------------------------------------------
# The autotune pass
# --------------------------------------------------------------------------

def plan_autotune(cfg=None, level_shapes: Optional[Sequence] = None, *,
                  measure: Optional[bool] = None, force: bool = False,
                  cache_path: Optional[str] = None, persist: bool = True,
                  iters: int = 5, warn_missing: bool = True
                  ) -> Optional[dict]:
    """Resolve (measure or load) the platform's plan table and APPLY it.

    The startup contract: the first run on a machine times the three
    calibration items on the actual device and persists the winners;
    every later process loads the table in microseconds. ``measure``:
    None (default) measures only when no usable entry exists; False
    never measures (CI / device-less machines — committed-table or
    static fallback); True with ``force`` re-measures over an existing
    entry. Returns the applied entry, or None on static fallback.

    After this returns, ``make_plan(..., backend="auto")``/``plan_for``
    resolve the measured budget (``describe()`` reports
    ``budget=measured``), the auto decode gate honors the measured sweep
    verdict, and ``resolve_stream_config(None)`` yields the measured
    ``diff_channel_stride``/``update_frac`` — end to end through
    ``TemporalCacheManager`` and the serve engines."""
    path = cache_path or default_table_path()
    plat = platform_key()
    entry = None
    table = load_table(path)
    if table is not None:
        entry = table.get("platforms", {}).get(plat)
        if entry is not None and not valid_entry(entry):
            warnings.warn(
                f"autotune entry for platform {plat!r} in {path!r} is "
                "partial/invalid; falling back to "
                + ("re-measurement" if measure is not False
                   else "static plan formulas"),
                RuntimeWarning, stacklevel=2)
            entry = None

    if entry is not None and not force:
        plan_lib.apply_tuned_plan_table(entry)
        return entry

    if measure is False:
        if warn_missing:
            warnings.warn(
                f"no usable autotune entry for platform {plat!r} "
                f"({path}) and measurement is disabled; static plan "
                "formulas stay in effect", RuntimeWarning, stacklevel=2)
        plan_lib.apply_tuned_plan_table(None)
        return None

    budget, budget_detail = measure_staging_budget(iters=iters)
    beneficial, speedup, decode_detail = measure_decode_sweep(
        cfg, level_shapes, iters=max(2, iters - 2))
    # the streaming crossover always measures at its own calibration
    # shape (STREAM_CALIB_LEVELS / d_model=256): the decode shape's
    # rebuild matmul is too small to expose the tradeoff
    stride, frac, stream_detail = measure_stream_crossover(iters=iters)
    entry = {
        "provenance": "measured",
        "platform": plat,
        "staging_budget_bytes": int(budget),
        "decode_sweep_beneficial": bool(beneficial),
        "decode_persistent_speedup": float(speedup),
        "stream": {"diff_channel_stride": int(stride),
                   "update_frac": float(frac)},
        "calibration": {"staging_budget": budget_detail,
                        "decode_sweep": decode_detail,
                        "stream_crossover": stream_detail},
    }
    if persist:
        try:
            save_entry(entry, path, plat)
        except OSError as e:
            warnings.warn(f"could not persist autotune table to {path!r} "
                          f"({e}); the measured entry applies to this "
                          "process only", RuntimeWarning, stacklevel=2)
    plan_lib.apply_tuned_plan_table(entry)
    return entry


_ENSURE_TRIED = False


def ensure_applied(cache_path: Optional[str] = None) -> Optional[dict]:
    """Load-only startup hook for the serve engines: apply the persisted
    per-platform entry once per process when none is applied yet. Never
    measures (startup must stay fast). An unreadable or malformed table
    warns and leaves the static formulas in effect (:func:`load_table`);
    any other error propagates."""
    global _ENSURE_TRIED
    if plan_lib.tuned_entry() is not None:
        return plan_lib.tuned_entry()
    if _ENSURE_TRIED:
        return None
    _ENSURE_TRIED = True
    return plan_autotune(measure=False, cache_path=cache_path,
                         warn_missing=False)


# --------------------------------------------------------------------------
# CLI (the CI leg: --no-measure --check)
# --------------------------------------------------------------------------

def _check(cfg, level_shapes) -> int:
    """Assert the applied table reaches the planner (budget=measured
    provenance) and that tuning never changes numerics: the auto-chosen
    backend under the tuned plan is bit-identical to the SAME backend
    chosen statically."""
    from repro import msda
    from repro.core.msdeform_attn import init_msdeform_attn

    entry = plan_lib.tuned_entry()
    if entry is None:
        print("[autotune --check] FAIL: no tuned entry applied "
              f"for platform {platform_key()!r}")
        return 2

    plan = plan_lib.plan_for(cfg, level_shapes, "auto", 64, 6)
    desc = plan.describe()
    if "budget=measured" not in desc:
        print("[autotune --check] FAIL: plan provenance is not measured: "
              + desc)
        return 2
    print(f"[autotune --check] provenance ok: {desc}")

    # tuned-vs-static bit-identity on a full planned attention pass
    key = jax.random.PRNGKey(5)
    params = init_msdeform_attn(key, cfg)
    n_in = plan.n_in
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, n_in, cfg.d_model))
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, n_in, cfg.d_model))
    from repro.core import nn
    refs = jnp.broadcast_to(
        nn.reference_points_for_levels(level_shapes)[None], (1, n_in, 2))
    tuned_plan = msda.make_plan(cfg, level_shapes, backend="auto")
    out_tuned, _ = msda.msda_attention(params, tuned_plan, q, refs, x)
    try:
        plan_lib.apply_tuned_plan_table(None)
        static_plan = msda.make_plan(cfg, level_shapes,
                                     backend=tuned_plan.backend)
        assert static_plan.budget_source == "static"
        out_static, _ = msda.msda_attention(params, static_plan, q, refs, x)
    finally:
        plan_lib.apply_tuned_plan_table(entry)
    if not np.array_equal(np.asarray(out_tuned), np.asarray(out_static)):
        print("[autotune --check] FAIL: tuned plan output differs from "
              f"static {tuned_plan.backend!r} output — tuning must change "
              "backend/budget choice, never numerics")
        return 2
    print(f"[autotune --check] bit-identity ok: auto->"
          f"{tuned_plan.backend} tuned == static "
          f"(budget {plan.staging_budget_bytes} B measured vs "
          f"{plan_lib.DEFAULT_WINDOW_STAGING_BUDGET} B static default)")
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--no-measure", action="store_true",
                    help="never time the device: committed-table or "
                    "static fallback (the CI leg)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even when a table entry exists")
    ap.add_argument("--table", default=None,
                    help="plan-table path (default results/autotune.json)")
    ap.add_argument("--check", action="store_true",
                    help="assert budget=measured provenance and "
                    "tuned-vs-static bit-identity; exit non-zero on failure")
    args = ap.parse_args(argv)

    cfg = _default_cfg()
    entry = plan_autotune(cfg, CALIB_LEVELS,
                          measure=False if args.no_measure else None,
                          force=args.force, cache_path=args.table)
    if entry is None:
        print(f"[autotune] platform {platform_key()!r}: no entry applied — "
              "static plan formulas in effect")
        return 2 if args.check else 0
    src = "loaded" if not args.force and not args.no_measure else \
        ("loaded (no-measure)" if args.no_measure else "measured")
    print(f"[autotune] platform {platform_key()!r} ({src}): "
          f"staging_budget={entry['staging_budget_bytes']} B, "
          f"decode_sweep_beneficial={entry['decode_sweep_beneficial']} "
          f"(speedup {entry.get('decode_persistent_speedup', 0):.2f}x), "
          f"stream stride={entry['stream']['diff_channel_stride']} "
          f"frac={entry['stream']['update_frac']}")
    if args.check:
        return _check(cfg, CALIB_LEVELS)
    return 0


if __name__ == "__main__":
    sys.exit(main())
