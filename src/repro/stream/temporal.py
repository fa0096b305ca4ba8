"""TemporalCacheManager — frame-to-frame value-cache reuse for streaming.

PRs 3–4 amortized the value-cache build across decoder *layers* (build
once per memory, sample everywhere). A video stream adds the temporal
axis: consecutive frames' encoder memories are a slowly-changing signal,
yet a naive deployment re-projects, re-compacts and re-stages the whole
table every frame. This manager treats the cache as PERSISTENT state:

  * **tile diff** — each incoming frame is diffed against ``x_ref`` (the
    memory as of each tile's last re-projection) at row-aligned tile
    granularity (:mod:`repro.stream.tiles`); only tiles whose max-abs
    feature delta clears ``delta_threshold`` are re-projected.
  * **static update capacity** — the incremental path re-projects at most
    ``update_rows`` table rows per frame (a static budget, same
    shape-discipline as FWP's static capacity): the dirty slots are
    gathered, projected as a (B, U, D) matmul, and scattered into the
    existing table — and into the persistent decode staging
    (``kernels/msgs_decode.update_staged_rows``) — via the existing
    pix2slot geometry. Frames with more dirty slots than the budget fall
    back to a full rebuild (host-side decision, two compiled paths, no
    per-pattern recompilation).
  * **streaming FWP** — sampling frequencies feed an EMA
    (:func:`repro.core.fwp.ema_update`) and the keep decision runs with
    keep-mask hysteresis (:func:`repro.core.fwp.build_fwp_state_hysteresis`),
    so ``keep_idx`` churn is bounded and the compact-slot windows stay
    stable; a keep-geometry transition (rare by construction) restages
    only the CHANGED levels on the next frame: each level's slots are
    one contiguous range of the compact table (``_compact_from_scores``
    keeps slots raster-ordered per level), so a transition confined to a
    subset of levels re-projects exactly those ranges and swaps the
    geometry arrays — a full rebuild happens only when every level's
    keep set moved (or FWP is off/mask, where there is no slot range).
  * **frozen quant scale** — partial updates fake-quant against the scale
    captured at the last full build (the whole table must share one
    grid); full rebuilds refresh it.

Accounting: every frame records mode (``rebuild`` | ``incremental``),
the staged-bytes delta actually moved, and what a full per-frame rebuild
would have staged — the rebuild-vs-incremental story
``benchmarks/fmap_reuse.py`` and the ``msda_stream_*`` microbench rows
report. With ``delta_threshold=0`` every tile is marked changed and the
incremental path reproduces a full rebuild bit-for-bit (parity-tested
across keep transitions in tests/test_stream.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fwp as fwp_lib
from repro.msda import plan as plan_lib
from repro.msda.cache import (MSDAValueCache, build_value_cache,
                              cache_act_scale, update_value_cache_rows)
from repro.msda.pipeline import MSDAPipelineState
from repro.obs import Observability
from repro.stream.tiles import TileGeometry, changed_tiles, tile_geometry


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static knobs of the temporal-reuse subsystem."""
    tile_rows: int = 2            # rows per diff tile (per level, row-aligned)
    delta_threshold: float = 1e-5  # max-abs feature drift a stale row may carry
    #   (0 => every tile changed every frame: the parity mode)
    update_frac: float = 0.25     # static per-frame re-projection budget as a
    #   fraction of the table's updatable rows (overridden by
    #   plan.stream_update_rows when the plan carries one)
    ema_alpha: float = 0.25       # streaming frequency EMA coefficient
    hyst_enter: float = 1.25      # k_enter = fwp_k * hyst_enter
    hyst_exit: float = 0.75       # k_exit  = fwp_k * hyst_exit
    diff_channel_stride: int = 1  # tile diffing probes every s-th feature
    #   channel (1 = exact). The diff (and the stored reference) is
    #   O(N_in·D/s): a real knob on bandwidth-starved hosts, at the cost
    #   of missing a change confined to unprobed channels — the
    #   re-projection itself always reads the FULL current features, so a
    #   probed diff only delays a sub-probe change, never corrupts rows
    #   it does update


def resolve_stream_config(scfg: Optional[StreamConfig] = None) -> StreamConfig:
    """The effective streaming knobs. An explicit config always wins,
    untouched. With no config, the defaults — overlaid with the
    autotuner's measured diff-vs-reprojection crossover
    (``diff_channel_stride`` / ``update_frac``) whenever a tuned plan
    table is applied (see :mod:`repro.msda.autotune`): "no config" means
    "measured best", not "hardcoded guess"."""
    if scfg is not None:
        return scfg
    tuned = plan_lib.tuned_stream_params()
    if not tuned:
        return StreamConfig()
    return dataclasses.replace(
        StreamConfig(),
        diff_channel_stride=int(tuned["diff_channel_stride"]),
        update_frac=float(tuned["update_frac"]))


def plan_slot_count(plan) -> int:
    """Updatable table rows of a plan's cache (compact: the capacity
    slots, excluding the zero sentinel; else every pixel row). The ONE
    derivation behind both the manager's slot space and the update cap."""
    cfg = plan.cfg
    if cfg.fwp_mode == "compact":
        return sum(fwp_lib.level_capacities(plan.level_shapes,
                                            cfg.fwp_capacity))
    return plan.n_in


def stream_update_cap(plan, update_frac: float) -> int:
    """The static incremental budget: rows re-projected per frame."""
    n_slots = plan_slot_count(plan)
    return max(1, min(n_slots, int(round(update_frac * n_slots))))


class TemporalCacheManager:
    """Persistent, incrementally updated MSDAValueCache for one stream.

    ``batch`` is the number of concurrent sessions sharing the manager
    (the streaming engine maps sessions onto batch slots); every slot
    carries its own diff reference, EMA scores and keep geometry rows.
    Host-side control flow picks between two jitted paths per frame
    (incremental update vs full rebuild); all heavy compute is jitted
    over arrays, so nothing retraces frame to frame."""

    def __init__(self, plan, value_params: dict,
                 scfg: Optional[StreamConfig] = None, *, batch: int = 1,
                 obs: Optional[Observability] = None):
        scfg = resolve_stream_config(scfg)
        if scfg.diff_channel_stride < 1:
            raise ValueError("diff_channel_stride must be >= 1")
        self.params = value_params
        self.scfg = scfg
        self.batch = int(batch)
        # unified telemetry: standalone managers get their own enabled
        # registry (the trace_counts view below must count for real);
        # the streaming engine passes its bundle in so manager counters
        # and engine spans share one registry/event log
        self.obs = obs if obs is not None else Observability.default(
            capacity=1024)
        m = self.obs.metrics
        self._m_traces = m.counter(
            "msda_traces_total",
            "jitted-path tracings by fn (trace-time spies: flat after "
            "warmup = session churn never retraces)")
        self._m_frames = m.counter(
            "stream_frames_total", "frames by update mode")
        self._m_rebuilds = m.counter(
            "stream_rebuilds_total", "full rebuilds by reason")
        self._m_staged = m.counter(
            "staged_bytes_total", "bytes actually staged, by update mode")
        self._m_dirty = m.gauge(
            "stream_dirty_slots", "dirty slot count of the last frame")
        self._m_span = m.histogram(
            "stream_span_seconds", "per-stage frame latency (label span=)")

        # ---- mutable stream state (host-held, arrays on device) ----------
        self.cache: Optional[MSDAValueCache] = None
        self.x_ref: Optional[jnp.ndarray] = None   # PROBED diff reference:
        #   the (B, N_in, ceil(D/stride)) channel slice of each tile's
        #   last-reprojected memory — all the diff ever reads
        self.ema: Optional[jnp.ndarray] = None
        self.fwp: Optional[fwp_lib.FWPState] = None
        self.act_scale: Optional[jnp.ndarray] = None
        self._cache_fwp: Optional[fwp_lib.FWPState] = None  # geometry the
        #   current cache was built with (stale detection self-heals)
        self._cache_plan = None                     # plan the current jitted
        #   paths were traced against — ``step`` detects a mid-stream swap
        #   (``mgr.plan = other_plan``) and reconfigures + rebuilds
        self._geometry_stale = True                 # first frame: full build
        self._pending_admit: set = set()            # slots scheduled for a
        #   per-slot admission build on the next frame (reset_slot)
        self.frame_index = 0
        self.rebuild_frames = 0
        self.partial_frames = 0                     # per-level restages
        self.staged_bytes_total = 0
        self.rebuild_bytes_total = 0                # per-frame-rebuild cost
        self.last_stats: Optional[dict] = None

        self._reconfigure(plan)

    @contextlib.contextmanager
    def _timed_span(self, name: str, **attrs):
        """Trace span + ``stream_span_seconds{span=name}`` histogram."""
        t0 = time.perf_counter()
        with self.obs.tracer.span(name, **attrs):
            yield
        self._m_span.observe(time.perf_counter() - t0, span=name)

    @property
    def trace_counts(self) -> dict:
        """Trace-time spies: each jitted impl bumps ``msda_traces_total``
        in its traced body, so the counts move ONLY on (re)compilation —
        tests assert session churn never retraces. A dict view over the
        registry counter (the same numbers production scrapes)."""
        return {k: int(self._m_traces.value(fn=k))
                for k in ("build", "frame", "restage")}

    def _reconfigure(self, plan) -> None:
        """(Re-)derive every plan-dependent static AND re-jit the compiled
        paths. Called at construction and when ``step`` detects the
        manager's plan was swapped mid-stream (a table-dtype or act-bits
        change, a backend move): the jitted closures close over the plan
        at TRACE time, so a swap without a re-jit would silently keep
        executing the old plan's build/update — wrong dtype, wrong
        accounting. The next frame after a swap always full-rebuilds
        (reason ``plan-change``): the existing table's codes live on the
        old plan's grid."""
        cfg = plan.cfg
        if cfg.fwp_mode not in ("off", "mask", "compact"):
            raise ValueError(f"unknown fwp_mode {cfg.fwp_mode!r}")
        self.plan = plan
        self.geo: TileGeometry = tile_geometry(plan.level_shapes,
                                               self.scfg.tile_rows)
        self._compact = cfg.fwp_mode == "compact"
        self.n_slots = plan_slot_count(plan)
        if self._compact:
            caps = fwp_lib.level_capacities(plan.level_shapes,
                                            cfg.fwp_capacity)
            self._n_rows = self.n_slots + 1            # + zero sentinel
            self._slot_windows = tuple(
                min(int(c), self._n_rows - 1) for c in caps)
        else:
            self._n_rows = plan.n_in
            self._slot_windows: Tuple[int, ...] = ()
        self._full_bytes = plan.table_bytes_for_rows(
            self._n_rows, with_indirection=self._compact)
        self.update_rows = plan.stream_update_rows \
            if plan.stream_update_rows is not None \
            else stream_update_cap(plan, self.scfg.update_frac)
        self.update_rows = max(1, min(self.update_rows, self.n_slots))
        self._incr_bytes = plan.table_bytes_for_rows(
            self.update_rows, with_indirection=False)
        # static per-level geometry for the partial (per-level) restage:
        # slot range [slot_offs[l], slot_offs[l+1]) and pixel range
        # [pix_starts[l], pix_starts[l]+h*w) of level l
        starts, _ = fwp_lib.level_starts(plan.level_shapes)
        self._pix_starts = tuple(int(s) for s in starts)
        if self._compact:
            caps = fwp_lib.level_capacities(plan.level_shapes,
                                            cfg.fwp_capacity)
            self._slot_offs = tuple(
                int(o) for o in np.concatenate([[0], np.cumsum(caps)]))
        else:
            self._slot_offs = ()

        self._jit_build = jax.jit(self._build_impl)
        self._jit_frame = jax.jit(self._frame_impl)
        self._jit_restage = jax.jit(self._restage_impl)
        k = float(cfg.fwp_k)
        scfg = self.scfg
        self._jit_hyst = jax.jit(lambda ema, prev: fwp_lib.build_fwp_state_hysteresis(
            ema, plan.level_shapes,
            k_enter=k * scfg.hyst_enter, k_exit=k * scfg.hyst_exit,
            mode=cfg.fwp_mode, capacity=cfg.fwp_capacity, prev=prev))

    # ---- jitted internals -------------------------------------------------
    def _build_impl(self, params, x_flat, fwp):
        self._m_traces.inc(fn="build")
        return build_value_cache(params, self.plan, x_flat,
                                 MSDAPipelineState(fwp=fwp))

    def _probe(self, x: jnp.ndarray) -> jnp.ndarray:
        s = self.scfg.diff_channel_stride
        return x if s == 1 else x[..., ::s]

    def _diff_impl(self, x_new, x_ref, keep_idx):
        changed = changed_tiles(self.geo, self._probe(x_new), x_ref,
                                self.scfg.delta_threshold)   # (B, n_tiles)
        t_of_p = jnp.asarray(self.geo.tile_of_pixel)
        if keep_idx is not None:                     # compact: slot -> tile
            slot_tile = t_of_p[keep_idx]             # (B, n_slots)
        else:                                        # dense: slot == pixel
            slot_tile = jnp.broadcast_to(t_of_p[None],
                                         (x_new.shape[0], self.n_slots))
        bidx = jnp.arange(x_new.shape[0])[:, None]
        slot_dirty = changed[bidx, slot_tile]        # (B, n_slots)
        return changed, slot_dirty, jnp.sum(slot_dirty, axis=1)

    def _update_impl(self, params, x_new, x_ref, v, staged, keep_idx,
                     keep_mask, changed, slot_dirty, act_scale, table_scale):
        # dirty slots first; the clean fillers that pad the static budget
        # are written back unchanged, so a clean row keeps exactly the
        # value of its last re-projection
        _, idx_u = jax.lax.top_k(slot_dirty.astype(jnp.float32),
                                 self.update_rows)
        idx_u = jnp.sort(idx_u, axis=1)
        refresh = jnp.take_along_axis(slot_dirty, idx_u, axis=1)
        # the ONE row-update path (cache.py): project + scatter into the
        # table and its decode staging. The temp cache just pairs the
        # traced arrays with this manager's static metadata. ``table_scale``
        # is the int8 table's FROZEN per-channel dequant scale: refreshed
        # rows re-quantize against it, so streaming stays int8 end-to-end
        # without ever materializing a dense float table.
        tmp = MSDAValueCache(v=v, pix2slot=None, keep_idx=keep_idx,
                             n_rows=self._n_rows,
                             slot_windows=self._slot_windows,
                             table_bytes=self._full_bytes, staged=staged,
                             scale=table_scale)
        upd, _ = update_value_cache_rows(params, self.plan, tmp, x_new,
                                         idx_u, act_scale=act_scale,
                                         keep_mask=keep_mask,
                                         refresh=refresh)
        pix_changed = changed[jnp.arange(x_new.shape[0])[:, None],
                              jnp.asarray(self.geo.tile_of_pixel)[None]]
        x_ref = jnp.where(pix_changed[..., None], self._probe(x_new), x_ref)
        return upd.v, upd.staged, x_ref

    def _frame_impl(self, params, x_new, x_ref, v, staged, keep_idx,
                    keep_mask, act_scale, table_scale):
        """ONE dispatch per frame: diff + speculative incremental update.

        The update runs unconditionally (its work is bounded by the
        static budget either way); the host commits it only when the
        dirty count fits the budget, else it discards the result and
        rebuilds — a rare path by construction, and fusing diff+update
        into one program keeps the per-frame dispatch count at one."""
        self._m_traces.inc(fn="frame")
        changed, slot_dirty, nd = self._diff_impl(x_new, x_ref, keep_idx)
        v, staged, x_ref = self._update_impl(
            params, x_new, x_ref, v, staged, keep_idx, keep_mask, changed,
            slot_dirty, act_scale, table_scale)
        return jnp.max(nd), jnp.sum(changed), v, staged, x_ref

    def _restage_impl(self, params, x_new, v, staged, new_keep_idx,
                      slot_idx, act_scale, table_scale):
        """Per-level partial restage: re-project the ``slot_idx`` slot
        ranges of the CHANGED levels from the current frame, addressed
        through the NEW keep geometry (slot -> pixel via
        ``new_keep_idx``), under the frozen act/table quant scales —
        the same row-update path as the incremental frame, just with a
        fresh slot->pixel map for the restaged ranges."""
        self._m_traces.inc(fn="restage")
        tmp = MSDAValueCache(v=v, pix2slot=None, keep_idx=new_keep_idx,
                             n_rows=self._n_rows,
                             slot_windows=self._slot_windows,
                             table_bytes=self._full_bytes, staged=staged,
                             scale=table_scale)
        upd, _ = update_value_cache_rows(params, self.plan, tmp, x_new,
                                         slot_idx, act_scale=act_scale)
        return upd.v, upd.staged

    # ---- host-side orchestration ------------------------------------------
    def _warm_fwp(self, batch: int) -> Optional[fwp_lib.FWPState]:
        """Warm-start keep state for fresh sessions: keep everything the
        capacity admits (k=0 thresholds), raster-first — the EMA then
        specializes it as real sampling frequencies arrive."""
        cfg = self.plan.cfg
        if cfg.fwp_mode == "off":
            return None
        ones = jnp.ones((batch, self.plan.n_in), jnp.float32)
        return fwp_lib.build_fwp_state(ones, self.plan.level_shapes, k=0.0,
                                       mode=cfg.fwp_mode,
                                       capacity=cfg.fwp_capacity)

    def _restore_meta(self, cache: MSDAValueCache) -> MSDAValueCache:
        """Re-pin the python-int metadata a jit boundary arrayified."""
        return cache._replace(n_rows=self._n_rows,
                              slot_windows=self._slot_windows,
                              table_bytes=self._full_bytes)

    def _full_build(self, x_new: jnp.ndarray) -> None:
        cfg = self.plan.cfg
        if cfg.fwp_mode != "off" and self.fwp is None:
            self.fwp = self._warm_fwp(x_new.shape[0])
            self.ema = jnp.ones((x_new.shape[0], self.plan.n_in),
                                jnp.float32)
        cache = self._jit_build(self.params, x_new, self.fwp)
        self.cache = self._restore_meta(cache)
        self.act_scale = cache_act_scale(self.cache, cfg)
        self.x_ref = self._probe(x_new)
        self._cache_fwp = self.fwp
        self._cache_plan = self.plan
        self._geometry_stale = False
        self._pending_admit.clear()    # a full build covers every slot

    def _transition_levels(self) -> Optional[Tuple[int, ...]]:
        """Which levels' keep geometry changed vs the cache's, or None
        when a partial restage is not applicable (not compact, no
        geometry to compare, nothing changed, EVERY level changed, or
        the restage plus the frame's incremental update would stage at
        least a full rebuild's bytes — then one build is cheaper)."""
        new, old = self.fwp, self._cache_fwp
        if not self._compact or new is None or old is None \
                or new.keep_idx is None or old.keep_idx is None:
            return None
        changed = []
        for li, (h, w) in enumerate(self.plan.level_shapes):
            s0, s1 = self._slot_offs[li], self._slot_offs[li + 1]
            p0 = self._pix_starts[li]
            if bool(jnp.any(new.keep_idx[:, s0:s1] != old.keep_idx[:, s0:s1])) \
                    or bool(jnp.any(new.pix2slot[:, p0:p0 + h * w]
                                    != old.pix2slot[:, p0:p0 + h * w])):
                changed.append(li)
        if not changed or len(changed) == len(self.plan.level_shapes):
            return None
        # the frame runs the incremental update after the restage, so the
        # partial path pays only when both stage fewer bytes than one
        # full rebuild
        if self._partial_bytes(changed) + self._incr_bytes \
                >= self._full_bytes:
            return None
        return tuple(changed)

    def _partial_bytes(self, levels) -> int:
        """Bytes a restage of ``levels`` stages: their slot rows under
        the plan's lane layout plus their share of the pix2slot
        indirection."""
        rows = sum(self._slot_offs[l + 1] - self._slot_offs[l]
                   for l in levels)
        pix = sum(h * w for l, (h, w) in enumerate(self.plan.level_shapes)
                  if l in levels)
        return self.plan.table_bytes_for_rows(
            rows, with_indirection=False) + pix * 4

    def _partial_restage(self, x_new: jnp.ndarray,
                         levels: Tuple[int, ...]) -> int:
        """Restage only the changed levels' contiguous slot ranges.

        Re-projects those ranges from the current frame through the NEW
        keep geometry, swaps ``keep_idx``/``pix2slot`` (and the decode
        staging's ``remap``) wholesale — they are whole-array int32
        geometry, cheap next to the value rows — and refreshes the diff
        reference for the changed levels' pixel ranges. Quant scales
        stay FROZEN (same grid as the surrounding table, exactly like
        the incremental row path). Returns the staged-bytes delta:
        the restaged rows under the plan's lane layout plus the changed
        levels' share of the pix2slot indirection."""
        slot_np = np.concatenate([
            np.arange(self._slot_offs[l], self._slot_offs[l + 1])
            for l in levels]).astype(np.int32)
        b = x_new.shape[0]
        slot_idx = jnp.broadcast_to(jnp.asarray(slot_np)[None],
                                    (b, len(slot_np)))
        v, staged = self._jit_restage(
            self.params, x_new, self.cache.v, self.cache.staged,
            self.fwp.keep_idx, slot_idx, self.act_scale, self.cache.scale)
        if staged is not None:
            staged = dataclasses.replace(staged, remap=self.fwp.pix2slot)
        self.cache = self.cache._replace(
            v=v, staged=staged, keep_idx=self.fwp.keep_idx,
            pix2slot=self.fwp.pix2slot)
        x_ref = self.x_ref
        probe = self._probe(x_new)
        for l in levels:
            h, w = self.plan.level_shapes[l]
            p0 = self._pix_starts[l]
            x_ref = x_ref.at[:, p0:p0 + h * w].set(probe[:, p0:p0 + h * w])
        self.x_ref = x_ref
        self._cache_fwp = self.fwp
        self._geometry_stale = False
        return self._partial_bytes(levels)

    def permute_slots(self, perm) -> None:
        """Reorder the batch (session) slots of every per-slot array.

        ``perm`` has gather semantics: new slot ``i`` takes the state
        previously held at slot ``perm[i]`` (so ``perm`` must be a
        permutation of ``range(batch)``). The streaming engine uses this
        to place sessions whose reference points cluster on adjacent
        batch slots, so their dirty-row scatters and decode staging
        share windows. A pure state permutation — no values change, no
        rebuild is triggered, and stepping after it is equivalent to
        stepping the unpermuted manager with permuted frame rows."""
        p = np.asarray(perm, np.int32)
        if sorted(p.tolist()) != list(range(self.batch)):
            raise ValueError(
                f"permute_slots needs a permutation of range({self.batch}), "
                f"got {p.tolist()}")
        pj = jnp.asarray(p)
        take = lambda a: None if a is None else jnp.take(a, pj, axis=0)
        if self.cache is not None:
            staged = self.cache.staged
            if staged is not None:
                staged = dataclasses.replace(
                    staged, v=take(staged.v), remap=take(staged.remap),
                    scale=take(staged.scale))
            self.cache = self.cache._replace(
                v=take(self.cache.v), pix2slot=take(self.cache.pix2slot),
                keep_idx=take(self.cache.keep_idx), staged=staged,
                scale=take(self.cache.scale))
        self.x_ref = take(self.x_ref)
        self.ema = take(self.ema)
        if self.act_scale is not None and self.act_scale.ndim > 0 \
                and self.act_scale.shape[0] == self.batch:
            self.act_scale = take(self.act_scale)
        for name in ("fwp", "_cache_fwp"):
            st = getattr(self, name)
            if st is not None:
                setattr(self, name, fwp_lib.FWPState(
                    keep_mask=take(st.keep_mask),
                    keep_idx=take(st.keep_idx),
                    pix2slot=take(st.pix2slot),
                    freq=take(st.freq)))
        if self._pending_admit:
            inv = {int(old): new for new, old in enumerate(p.tolist())}
            self._pending_admit = {inv[s] for s in self._pending_admit}

    def step(self, x_new, force_full: bool = False
             ) -> Tuple[MSDAValueCache, dict]:
        """Ingest one frame's memory; returns (cache, frame stats).

        The cache is persistent: an incremental frame scatter-updates the
        existing table (and its decode staging) in place; a keep-geometry
        transition confined to a subset of levels restages only those
        levels' contiguous slot ranges (mode ``partial``); a full rebuild
        happens only on the first frame, on whole-geometry keep
        transitions, on ``force_full`` (session admission), or when the
        dirty-slot count exceeds the static update budget."""
        x_new = jnp.asarray(x_new)
        assert x_new.ndim == 3 and x_new.shape[1] == self.plan.n_in, \
            (x_new.shape, self.plan.n_in)
        n_dirty = tiles_hit = 0
        plan_change = self.cache is not None \
            and self.plan is not self._cache_plan
        if plan_change:
            # mid-stream plan swap (table dtype, act_bits, backend, ...):
            # the jitted paths and accounting were traced against the old
            # plan and the table's codes live on the old plan's grid —
            # reconfigure everything and rebuild from this frame's memory
            old = self._cache_plan
            self._reconfigure(self.plan)
            if (self.plan.level_shapes != old.level_shapes
                    or self.plan.cfg.fwp_mode != old.cfg.fwp_mode
                    or self.plan.cfg.fwp_capacity != old.cfg.fwp_capacity):
                # keep state rows were derived under the OLD geometry
                self.fwp = self.ema = None
        keep_transition = self._geometry_stale and self.cache is not None \
            and not plan_change
        restaged_levels: Tuple[int, ...] = ()
        partial_bytes = 0
        if keep_transition and not force_full:
            # per-level partial restage: each level's slots are ONE
            # contiguous range of the compact table, so a transition that
            # only moved some levels' keep sets restages those ranges
            # instead of rebuilding the whole table. The restage swaps
            # the geometry and re-projects the changed levels from this
            # frame; the UNCHANGED levels' feature drift then flows
            # through the ordinary incremental diff below.
            partial = self._transition_levels()
            if partial:
                restaged_levels = partial
                with self._timed_span("scatter", kind="partial-restage",
                                          levels=partial):
                    partial_bytes = self._partial_restage(x_new, partial)
        admitted: Tuple[int, ...] = ()
        admit_bytes = 0
        if self._pending_admit and self.cache is not None \
                and not self._geometry_stale and not force_full \
                and not plan_change:
            # per-slot session admission: rebuild ONLY the joining slots'
            # rows from their own frames; the rest of the batch proceeds
            # incrementally below (the admitted slots' diff reference was
            # just refreshed, so they contribute zero dirty tiles)
            admitted = tuple(sorted(self._pending_admit))
            self._pending_admit.clear()
            with self._timed_span("scatter", kind="admission",
                                      slots=admitted):
                admit_bytes = self._admit_slots(x_new, admitted)
        if self.cache is None or self._geometry_stale or force_full \
                or plan_change:
            mode, reason = "rebuild", (
                "first-frame" if self.cache is None else
                "plan-change" if plan_change else
                "keep-transition" if keep_transition else "forced")
            with self._timed_span("rebuild", reason=reason):
                self._full_build(x_new)
            staged_bytes = self._full_bytes
        else:
            keep_idx = self.cache.keep_idx if self._compact else None
            keep_mask = None
            if self.plan.cfg.fwp_mode == "mask":
                keep_mask = self.fwp.keep_mask
            with self._timed_span("diff"):
                nd, tiles, v, staged, x_ref = self._jit_frame(
                    self.params, x_new, self.x_ref, self.cache.v,
                    self.cache.staged, keep_idx, keep_mask, self.act_scale,
                    self.cache.scale)
                n_dirty = int(nd)
                tiles_hit = int(tiles)
            if n_dirty > self.update_rows:
                # speculative update discarded: dirt exceeds the static
                # budget, the table must be rebuilt wholesale
                mode, reason = "rebuild", "dirty>budget"
                with self._timed_span("rebuild", reason=reason):
                    self._full_build(x_new)
                staged_bytes = partial_bytes + admit_bytes \
                    + self._full_bytes
            else:
                mode = "partial" if restaged_levels else "incremental"
                reason = "keep-transition" if restaged_levels else ""
                self.cache = self.cache._replace(v=v, staged=staged)
                self.x_ref = x_ref
                staged_bytes = partial_bytes + admit_bytes \
                    + self._incr_bytes
        self.frame_index += 1
        self.rebuild_frames += mode == "rebuild"
        self.partial_frames += mode == "partial"
        self.staged_bytes_total += staged_bytes
        self.rebuild_bytes_total += self._full_bytes
        self.last_stats = {
            # scope: the whole BATCH (all sessions sharing this manager
            # advance together) — per-session consumers must not sum
            # staged_bytes across sessions of one frame
            "scope": "batch",
            "frame": self.frame_index - 1, "mode": mode, "reason": reason,
            "staged_bytes": staged_bytes,
            "rebuild_bytes": self._full_bytes,
            "n_dirty": n_dirty, "tiles_changed": tiles_hit,
            "keep_transition": bool(keep_transition),
            "restaged_levels": restaged_levels,
            "admitted_slots": admitted,
            "update_rows": self.update_rows,
        }
        # unified metrics mirror of last_stats (host-side, outside jit)
        self._m_frames.inc(mode=mode)
        self._m_staged.inc(staged_bytes, mode=mode)
        if mode == "rebuild":
            self._m_rebuilds.inc(reason=reason)
        self._m_dirty.set(n_dirty)
        return self.cache, self.last_stats

    def observe(self, freq: jnp.ndarray) -> bool:
        """Feed back one frame's sampling frequencies (B, N_in).

        Updates the streaming EMA and re-derives the keep decision with
        hysteresis; returns True when the keep GEOMETRY changed vs what
        the current cache was built with (the next ``step`` then does a
        full rebuild). No-op when FWP is off."""
        cfg = self.plan.cfg
        if cfg.fwp_mode == "off":
            return False
        freq = jnp.asarray(freq, jnp.float32)
        self.ema = freq if self.ema is None \
            else fwp_lib.ema_update(self.ema, freq, self.scfg.ema_alpha)
        self.fwp = self._jit_hyst(self.ema, self.fwp)
        stale = self._fwp_geometry_differs(self.fwp, self._cache_fwp)
        self._geometry_stale = stale
        return stale

    @staticmethod
    def _fwp_geometry_differs(a: Optional[fwp_lib.FWPState],
                              b: Optional[fwp_lib.FWPState]) -> bool:
        if a is None or b is None:
            return a is not b
        if a.keep_idx is not None:
            return bool(jnp.any(a.keep_idx != b.keep_idx)) \
                or bool(jnp.any(a.pix2slot != b.pix2slot))
        return bool(jnp.any(a.keep_mask != b.keep_mask))

    def _admit_slots(self, x_new: jnp.ndarray, slots: Tuple[int, ...]
                     ) -> int:
        """Per-slot admission: build each admitted slot's table rows from
        its OWN frame (a batch-1 build through the already-traced
        ``_jit_build`` — batch 1 is one extra trace at most, shared by
        every admission) and scatter them into this slot's rows of the
        persistent cache, its decode staging, the diff reference and the
        cache-geometry record. Every other slot's state is untouched, so
        the rest of the batch rides the ordinary incremental path — a
        session joining never rebuild-storms its neighbours. Returns the
        staged-bytes delta (the admitted slots' share of a full build)."""
        for slot in slots:
            fwp1 = None
            if self.fwp is not None:
                f = self.fwp
                fwp1 = fwp_lib.FWPState(
                    keep_mask=f.keep_mask[slot:slot + 1],
                    keep_idx=None if f.keep_idx is None
                    else f.keep_idx[slot:slot + 1],
                    pix2slot=None if f.pix2slot is None
                    else f.pix2slot[slot:slot + 1],
                    freq=f.freq[slot:slot + 1])
            built = self._restore_meta(
                self._jit_build(self.params, x_new[slot:slot + 1], fwp1))
            c = self.cache
            srow = lambda a, b: None if a is None else a.at[slot].set(b[0])
            staged = c.staged
            if staged is not None:
                bs = built.staged
                staged = dataclasses.replace(
                    staged, v=staged.v.at[slot].set(bs.v[0]),
                    remap=srow(staged.remap, bs.remap),
                    scale=srow(staged.scale, bs.scale))
            self.cache = c._replace(
                v=c.v.at[slot].set(built.v[0]),
                pix2slot=srow(c.pix2slot, built.pix2slot),
                keep_idx=srow(c.keep_idx, built.keep_idx),
                scale=srow(c.scale, built.scale), staged=staged)
            self.x_ref = self.x_ref.at[slot].set(self._probe(x_new)[slot])
            if self._cache_fwp is not None:
                g, f = self._cache_fwp, self.fwp
                self._cache_fwp = fwp_lib.FWPState(
                    keep_mask=g.keep_mask.at[slot].set(f.keep_mask[slot]),
                    keep_idx=None if g.keep_idx is None
                    else g.keep_idx.at[slot].set(f.keep_idx[slot]),
                    pix2slot=None if g.pix2slot is None
                    else g.pix2slot.at[slot].set(f.pix2slot[slot]),
                    freq=g.freq.at[slot].set(f.freq[slot]))
        # accounting unit is per (batch, head-group) = per batch element:
        # k admitted slots cost their k/batch share of a full build
        return (self._full_bytes * len(slots) + self.batch - 1) \
            // self.batch

    def reset_slot(self, slot: int) -> None:
        """Reset one batch slot for a newly admitted session: warm-start
        its EMA/keep rows and schedule a PER-SLOT build on the next frame
        (``_admit_slots``). Falls back to flagging a full rebuild before
        the first frame (nothing to scatter into yet) and under frozen
        per-tensor activation quantization (``act_scale``): the admitted
        slot's build would re-derive the shared act grid, so exactness
        requires rebuilding the whole batch against one fresh scale."""
        if self.cache is None or self.act_scale is not None:
            self._geometry_stale = True
        else:
            self._pending_admit.add(slot)
        if self.ema is None:
            return
        self.ema = self.ema.at[slot].set(1.0)
        warm = self._warm_fwp(1)
        self.fwp = fwp_lib.FWPState(
            keep_mask=self.fwp.keep_mask.at[slot].set(warm.keep_mask[0]),
            keep_idx=None if self.fwp.keep_idx is None
            else self.fwp.keep_idx.at[slot].set(warm.keep_idx[0]),
            pix2slot=None if self.fwp.pix2slot is None
            else self.fwp.pix2slot.at[slot].set(warm.pix2slot[0]),
            freq=self.fwp.freq.at[slot].set(1.0))

    def pipeline_state(self) -> MSDAPipelineState:
        """The chain state a consumer threads through its layers: the
        streaming FWP link plus this frame's temporal-reuse accounting."""
        return MSDAPipelineState(fwp=self.fwp).with_stream(self.last_stats)

    def report(self) -> dict:
        """Cumulative rebuild-vs-incremental accounting."""
        staged = max(self.staged_bytes_total, 1)
        return {
            "frames": self.frame_index,
            "table_dtype": self.plan.table_dtype,
            "rebuild_frames": self.rebuild_frames,
            "partial_frames": self.partial_frames,
            "incremental_frames": self.frame_index - self.rebuild_frames
            - self.partial_frames,
            "update_rows": self.update_rows,
            "n_slots": self.n_slots,
            "staged_bytes_total": self.staged_bytes_total,
            "rebuild_bytes_total": self.rebuild_bytes_total,
            "bytes_ratio": self.rebuild_bytes_total / staged,
            "full_bytes_per_frame": self._full_bytes,
            "incremental_bytes_per_frame": self._incr_bytes,
        }
