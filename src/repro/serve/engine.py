"""Continuous-batching DETR serving over AOT-compiled shape buckets.

The serving analogue of the paper's "DEFA rivals GPUs" comparison, built
the way MaxText's offline-inference harness serves LLMs:

  * **AOT shape buckets** — a small set of resolution/level-shape buckets
    is derived from the detector config (``serve/buckets.py``) and each
    bucket's forward is compiled at STARTUP via
    ``jax.jit(...).lower().compile()``. Incoming images route to the
    smallest bucket they fit (padding up); oversized images are rejected
    at admission. After warmup nothing ever retraces — the engine carries
    a compile-count spy (``compile_count``) that tests assert stays flat
    under mixed load.
  * **continuous batching** — requests queue per bucket; every
    :meth:`DetrServeEngine.step` dispatches the deepest bucket's
    micro-batch. Sessions of the streaming engine join/leave batch slots
    between steps without recompiling (per-slot admission in
    ``stream/temporal.py`` — no batch-wide rebuild storm).
  * **pipelined post-processing** — top-k decode, box emission and
    per-request callbacks run on a background worker thread
    (``serve/postproc.py``): the device launches step N+1 while step N's
    outputs are still being decoded on the host.

Every forward builds ONE shared :class:`~repro.msda.MSDAValueCache` from
the encoder memory and all decoder layers sample it (build-once,
sample-everywhere). The seed-era token-decode engine lives on in
``serve/lm.py``; drivers are examples/detr_serve.py (batch + sustained
load) and examples/detr_stream.py (streaming sessions)."""
from __future__ import annotations

import dataclasses
import time
import threading
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nn
from repro.obs import Observability, hlo_scopes
from repro.serve.buckets import BucketRouter, ShapeBucket, derive_buckets
from repro.serve.postproc import (PostprocWorker, StarvationError,
                                  softmax_np, topk_detections)


@dataclasses.dataclass
class DetrRequest:
    rid: int
    image: np.ndarray                     # (3, H, W) float32, H/W <= bucket
    # filled by the engine:
    cls_logits: Optional[np.ndarray] = None   # (Nq, C+1) as served
    cls_probs: Optional[np.ndarray] = None    # (Nq, C+1) softmax
    boxes: Optional[np.ndarray] = None        # (Nq, 4) cxcywh
    detections: Optional[dict] = None         # top-k decode (postproc stage)
    done: bool = False
    bucket: Optional[int] = None              # resolution routed to
    error: Optional[str] = None               # admission rejection reason
    callback: Optional[Callable] = None       # invoked on completion
    t_submit: float = 0.0
    t_dispatched: float = 0.0                 # its batch's dispatch returned
    t_done: float = 0.0
    step: Optional[int] = None                # engine step that dispatched
    #   it: the ``step`` of its ``serve.dispatch``/``serve.fetch`` spans
    span_queue: Optional[str] = None          # open "queue" span id — the
    #   request context that carries the trace across the worker thread


class DetrServeEngine:
    """Bucketed continuous-batching DETR detection server.

    ``resolutions`` selects the AOT shape buckets (default: one bucket at
    ``cfg.img_size``). Each bucket's forward is compiled once at
    construction for the static ``(max_batch, 3, r, r)`` shape; the model
    params are resolution-independent, so every bucket serves the same
    weights. ``submit`` routes (and may reject) immediately; ``step``
    dispatches one micro-batch from the deepest bucket queue and hands
    the device outputs to the post-processing stage, which runs on a
    worker thread when ``pipeline_postproc`` is set (the default) — the
    two modes share one decode path and are bit-identical."""

    def __init__(self, cfg, params, *, max_batch: int = 8,
                 backend: Optional[str] = None,
                 resolutions: Optional[tuple] = None,
                 pipeline_postproc: bool = True, topk: int = 5,
                 obs: Optional[Observability] = None):
        from repro.core.detector import detector_apply
        from repro.msda.autotune import ensure_applied
        ensure_applied()   # load-only: the committed/measured plan table,
        #   so bucket derivation below sees the tuned budgets (never
        #   times anything)
        self.cfg = cfg
        self.params = params
        self.max_batch = int(max_batch)
        self.backend = backend
        self.topk = int(topk)
        # per-engine observability: own registry (counters are exact for
        # THIS engine) + tracer; Observability.disabled() is the zero-cost
        # uninstrumented mode the overhead benchmark compares against.
        # Everything below touches it strictly outside jit, except the
        # compile counter, whose bump runs at TRACE time by design.
        self.obs = obs if obs is not None else Observability.default()
        m = self.obs.metrics
        self._m_compiles = m.counter(
            "msda_compiles_total",
            "detector forward tracings per bucket (trace-time spy: flat "
            "after AOT warmup = zero retraces)")
        self._m_requests = m.counter(
            "serve_requests_total", "requests by bucket and outcome")
        self._m_qdepth = m.gauge(
            "serve_queue_depth", "admitted requests waiting per bucket")
        self._m_backlog = m.gauge(
            "serve_postproc_backlog", "batches queued to the postproc worker")
        self._m_latency = m.histogram(
            "serve_request_latency_seconds",
            "submit-to-callback latency per completed request")
        self._m_staged = m.counter(
            "staged_bytes_total",
            "bytes staged to device per the plan's static accounting")
        if resolutions is None:
            resolutions = (cfg.img_size,)
        self.buckets = derive_buckets(cfg, resolutions, backend=backend)
        self.router = BucketRouter(self.buckets)
        self._bucket_by_res = {b.resolution: b for b in self.buckets}
        self.queues: dict[int, deque[DetrRequest]] = {
            b.resolution: deque() for b in self.buckets}
        self.finished: list[DetrRequest] = []
        self.rejected: list[DetrRequest] = []
        self._lock = threading.Lock()
        self._steps = 0            # batches dispatched (this thread)
        self._fetched = 0          # batches whose results reached the host
        #   (post-processing stage only): the difference is what is in
        #   flight on the device
        self._compiled = {}
        self.compile_seconds: dict[int, float] = {}
        for b in self.buckets:
            # compile-count spy: the increment executes at TRACE time
            # only, so after the AOT warmup below it must never move
            # again — tests/test_serve.py asserts zero recompiles under
            # mixed load against this registry counter
            def fwd(p, img, _cfg=b.cfg, _res=b.resolution):
                self._m_compiles.inc(bucket=str(_res))
                return detector_apply(p, _cfg, img, backend=self.backend)
            spec = jax.ShapeDtypeStruct(
                (self.max_batch, 3, b.resolution, b.resolution), jnp.float32)
            t0 = time.perf_counter()
            self._compiled[b.resolution] = \
                jax.jit(fwd).lower(self.params, spec).compile()
            self.compile_seconds[b.resolution] = time.perf_counter() - t0
            self.obs.tracer.event("plan", engine="DetrServeEngine",
                                  bucket=b.resolution,
                                  plan=b.plan.snapshot())
        self._post = PostprocWorker(self._complete,
                                    pipelined=pipeline_postproc,
                                    obs=self.obs)

    @property
    def compile_count(self) -> int:
        """Total detector tracings across buckets — the zero-retrace spy,
        now a view over the ``msda_compiles_total`` registry counter."""
        return int(self._m_compiles.total())

    # ---- introspection -----------------------------------------------------
    def describe(self) -> str:
        lines = []
        for b in self.buckets:
            d = b.plan.describe()
            if b.plan.backend == "pallas_decode":
                # the serving-relevant consequence of the persistent
                # decode plan: every request batch stages the compact
                # table once and all decoder layers sample the staged
                # block
                d += " [persistent decode: table staged once per memory]"
            lines.append(f"bucket {b.resolution}px: {d}")
        return "\n".join(lines)

    def bucket_table(self) -> list:
        return self.router.table()

    def op_scopes(self, resolution: int) -> dict:
        """``{HLO instruction name: scope path}`` of the bucket's compiled
        forward (:func:`repro.obs.hlo_scopes`): the layer each device
        operation of a profiler trace belongs to, e.g.
        ``encoder/block_3/msda/sample``."""
        return hlo_scopes(self._compiled[resolution])

    def pending(self) -> int:
        """Requests admitted but not yet dispatched to the device."""
        return sum(len(q) for q in self.queues.values())

    # ---- admission ---------------------------------------------------------
    def submit(self, req: DetrRequest) -> bool:
        """Route a request to its bucket queue; returns False (and records
        the reason on ``req.error``) when admission control rejects it."""
        with self.obs.tracer.span("serve.submit", rid=req.rid):
            req.t_submit = time.perf_counter()
            bucket, reason = self.router.admit(req.image)
            if bucket is None:
                req.error = reason
                self._m_requests.inc(bucket="none", outcome="rejected")
                with self._lock:
                    self.rejected.append(req)
                return False
            res = bucket.resolution
            req.bucket = res
            # the "queue" span opens here and is closed by step() at dispatch;
            # its id rides on the request (the cross-thread trace context)
            req.span_queue = self.obs.tracer.start("queue", rid=req.rid,
                                                   t=req.t_submit, bucket=res)
            self._m_requests.inc(bucket=str(res), outcome="admitted")
            self.queues[res].append(req)
            self._m_qdepth.set(len(self.queues[res]), bucket=str(res))
            return True

    # ---- one engine step ---------------------------------------------------
    def step(self) -> int:
        """Dispatch one micro-batch from the deepest bucket queue (padded
        to the static batch; ties pick the cheaper/smaller bucket).
        Returns the number of requests dispatched — completion happens in
        the post-processing stage."""
        res = max((r for r, q in self.queues.items() if q),
                  key=lambda r: (len(self.queues[r]), -r), default=None)
        if res is None:
            return 0
        q = self.queues[res]
        batch = [q.popleft() for _ in range(min(self.max_batch, len(q)))]
        tr = self.obs.tracer
        self._m_qdepth.set(len(q), bucket=str(res))
        for req in batch:
            if req.span_queue:
                tr.end(req.span_queue)
                req.span_queue = None
        step = self._steps
        self._steps += 1
        with tr.span("serve.dispatch", step=step, n=len(batch), bucket=res,
                     inflight=step - self._fetched):
            imgs = np.zeros((self.max_batch, 3, res, res), np.float32)
            for i, req in enumerate(batch):
                im = np.asarray(req.image, np.float32)
                imgs[i, :, :im.shape[1], :im.shape[2]] = im     # pad up
            cls_logits, boxes, _aux = self._compiled[res](self.params,
                                                          jnp.asarray(imgs))
        t = time.perf_counter()
        for req in batch:
            req.step, req.t_dispatched = step, t
        # build-once value cache per dispatched memory (static accounting)
        self._m_staged.inc(
            self._bucket_by_res[res].plan.cache_table_bytes, mode="build")
        # hand the device arrays straight to the postproc stage: the
        # worker's np.asarray blocks on the transfer while this thread is
        # free to dispatch the next bucket's micro-batch
        self._post.submit((step, batch, cls_logits, boxes))
        self._m_backlog.set(self._post.backlog)
        return len(batch)

    def _complete(self, item) -> None:
        step, batch, cls_logits, boxes = item
        tr = self.obs.tracer
        with tr.span("serve.fetch", step=step, n=len(batch)):
            cls_logits = np.asarray(cls_logits)
            probs = softmax_np(cls_logits)
            boxes = np.asarray(boxes)
        self._fetched += 1
        with tr.span("postproc", step=step, n=len(batch)):
            for i, req in enumerate(batch):
                req.cls_logits = cls_logits[i]
                req.cls_probs = probs[i]
                req.boxes = boxes[i]
                req.detections = topk_detections(probs[i], boxes[i],
                                                 self.topk)
                req.t_done = time.perf_counter()
                req.done = True
                if req.callback is not None:
                    with tr.span("callback", rid=req.rid):
                        req.callback(req)
                self._m_latency.observe(req.t_done - req.t_submit,
                                        bucket=str(req.bucket))
                self._m_requests.inc(bucket=str(req.bucket),
                                     outcome="completed")
                with self._lock:
                    self.finished.append(req)

    def drain(self) -> None:
        """Barrier on the post-processing stage only (no new dispatches)."""
        self._post.drain()

    def run_until_drained(self, max_steps: int = 10000
                          ) -> list[DetrRequest]:
        steps = 0
        while self.pending() and steps < max_steps:
            self.step()
            steps += 1
        self._post.drain()
        if self.pending():
            now = time.perf_counter()
            raise StarvationError({
                "engine": "DetrServeEngine", "steps": steps,
                "queued": {r: len(q) for r, q in self.queues.items() if q},
                # per-bucket age of the head (oldest) queued request,
                # from the same perf_counter timeline as the queue spans
                "oldest_age_s": {r: round(now - q[0].t_submit, 6)
                                 for r, q in self.queues.items() if q},
                "finished": len(self.finished),
                "rejected": len(self.rejected)})
        self.obs.flush_metrics()
        return self.finished

    def close(self) -> None:
        """Shut down the post-processing worker (joins its thread);
        idempotent, and ``submit``/``step`` pipelining into the worker
        raises once closed. Flushes a final metrics snapshot into the
        JSONL event log (when one is attached) and closes the sink."""
        self._post.close()
        self.obs.flush_metrics()
        self.obs.close()

    def __enter__(self) -> "DetrServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


# --------------------------------------------------------------------------
# Streaming DETR detection — temporal value-cache reuse across video frames
# --------------------------------------------------------------------------

@dataclasses.dataclass
class StreamSession:
    """One live video stream occupying a batch slot of the engine.

    Each entry of ``results`` carries the frame's detections plus the
    manager's frame accounting under ``"stream"`` — that dict is
    BATCH-scoped (``stream["scope"] == "batch"``): all sessions advance
    in one batched update, so its staged-bytes/dirty counts describe the
    whole step, not this session's share."""
    sid: int
    slot: int
    queue: deque = dataclasses.field(default_factory=deque)
    results: list = dataclasses.field(default_factory=list)
    frames_done: int = 0
    t_queue: deque = dataclasses.field(default_factory=deque)  # submit
    #   times (perf_counter) parallel to ``queue`` — starvation ages


class StreamingDetrEngine:
    """Streaming detection over persistent, incrementally updated caches.

    The temporal extension of :class:`DetrServeEngine`'s slot model: up
    to ``max_sessions`` concurrent video sessions each occupy one batch
    slot, and ONE batched :class:`~repro.stream.TemporalCacheManager`
    carries every slot's persistent ``MSDAValueCache``, diff reference,
    streaming-EMA frequency scores and hysteresis keep state. Per
    :meth:`step`, each session's next frame memory is stacked into the
    static batch (idle slots replay their diff reference, contributing
    zero dirty tiles), the manager applies ONE incremental update (or a
    full rebuild — first frame, keep transition, or over-budget dirt),
    the decoder + heads run one jitted forward against the shared cache,
    and the sampled frequencies feed back into the EMA.

    Sessions join and leave slots BETWEEN steps without recompiling and
    without disturbing their neighbours: admission schedules a per-slot
    build in the manager (batch-1 build scattered into the slot's rows)
    while every other session rides the ordinary incremental path — the
    continuous-batching contract of the serve tentpole.

    Sessions submit encoder MEMORIES (N_in, D) — in a full pipeline the
    backbone+encoder run per frame upstream; the temporal reuse targets
    the value-cache build (projection + compaction + staging), which is
    what rebuilding per frame would pay per decoder stack."""

    def __init__(self, attn_cfg, decoder_cfg, params: dict,
                 level_shapes, *, max_sessions: int = 2,
                 backend: Optional[str] = None, stream_cfg=None,
                 update_fwp: bool = True,
                 obs: Optional[Observability] = None):
        from repro.msda import MSDAPlan, backend_info, make_plan  # noqa: F401
        from repro.msda.autotune import ensure_applied
        from repro.stream import (TemporalCacheManager,
                                  resolve_stream_config, stream_update_cap)
        ensure_applied()   # load-only tuned plan table: budgets for the
        #   plan below, measured stream crossover for the default scfg
        self.attn_cfg = attn_cfg
        self.dec_cfg = decoder_cfg
        self.params = params
        self.max_sessions = int(max_sessions)
        self._update_fwp = bool(update_fwp) and attn_cfg.fwp_mode != "off"
        scfg = resolve_stream_config(stream_cfg)
        if backend is not None and backend != "auto" \
                and backend_info(backend).raster_only:
            backend = "auto"             # same fallback as decoder_plan
        plan = make_plan(attn_cfg, level_shapes, backend=backend,
                         n_queries=decoder_cfg.n_queries,
                         n_consumers=decoder_cfg.n_layers)
        self.plan = dataclasses.replace(
            plan, stream_update_rows=stream_update_cap(plan,
                                                       scfg.update_frac))
        # engine and manager share ONE bundle: the manager's frame/staged
        # counters and the engine's spans land in the same registry/log
        self.obs = obs if obs is not None else Observability.default()
        self._m_span = self.obs.metrics.histogram(
            "stream_span_seconds", "per-stage frame latency (label span=)")
        self._m_frame_latency = self.obs.metrics.histogram(
            "stream_frame_latency_seconds", "full step latency per frame")
        self.obs.tracer.event("plan", engine="StreamingDetrEngine",
                              plan=self.plan.snapshot())
        self.mgr = TemporalCacheManager(
            self.plan, params["decoder"]["value"], scfg,
            batch=self.max_sessions, obs=self.obs)
        self.sessions: dict[int, StreamSession] = {}
        self._free_slots = list(range(self.max_sessions))
        self._next_sid = 0
        self._last_memory = None       # (B, N_in, D) last served batch —
        #   idle slots replay their row (zero dirty tiles by construction)
        self._slot_centroid: dict[int, np.ndarray] = {}  # slot -> mean
        #   predicted (cx, cy) of the last served frame — the session's
        #   reference-point cluster, what reorder_sessions() sorts by
        self._fwd = jax.jit(self._fwd_impl)

    def describe(self) -> str:
        r = self.mgr
        return (self.plan.describe()
                + f" [streaming: {self.max_sessions} sessions, "
                f"tile_rows={r.scfg.tile_rows}, "
                f"update<={r.update_rows}/{r.n_slots} rows/frame]")

    def capacity_estimate(self, budget_bytes: Optional[int] = None) -> dict:
        """Sessions-per-chip estimate: how many concurrent streams'
        persistent value tables fit one staging budget (default the
        resolved window budget — env pin, else the autotuner's MEASURED
        ceiling when a tuned table is applied, else the 4 MB static
        formula; ``budget_source`` records which), per table dtype. Each
        session's cost is its full table (rows x lanes x itemsize, + the
        int8 scale row, + the pix2slot indirection when compact) — the
        thing a slot holds resident between frames. The f32-vs-int8 rows
        are the serving story of the int8 table: ~4x more sessions per
        chip at the same budget."""
        from repro.msda import staging_budget_source, window_staging_budget
        source = "caller"
        if budget_bytes is None:
            budget_bytes = window_staging_budget()
            source = staging_budget_source()
        per_dtype = {}
        for d in ("float32", "int8"):
            p = dataclasses.replace(self.plan, table_dtype=d)
            per = p.table_bytes_for_rows(self.mgr._n_rows,
                                         with_indirection=self.mgr._compact)
            per_dtype[d] = {"bytes_per_session": per,
                            "sessions": budget_bytes // per}
        return {"budget_bytes": budget_bytes,
                "budget_source": source,
                "table_dtype": self.plan.table_dtype,
                "rows_per_session": self.mgr._n_rows,
                "per_dtype": per_dtype}

    # ---- session lifecycle -------------------------------------------------
    def open_session(self) -> int:
        if not self._free_slots:
            raise RuntimeError(
                f"all {self.max_sessions} streaming slots are busy")
        slot = self._free_slots.pop(0)
        sid = self._next_sid
        self._next_sid += 1
        self.sessions[sid] = StreamSession(sid=sid, slot=slot)
        # warm-start the slot's EMA/keep rows and schedule a PER-SLOT
        # admission build: the next step rebuilds only this slot's table
        # rows from its own frame, other sessions ride the incremental
        # path — joining never rebuild-storms the whole batch
        self.mgr.reset_slot(slot)
        return sid

    def close_session(self, sid: int) -> StreamSession:
        sess = self.sessions.pop(sid)
        self._free_slots.append(sess.slot)
        self._slot_centroid.pop(sess.slot, None)
        return sess

    def submit_frame(self, sid: int, memory: np.ndarray) -> None:
        """Queue one frame's encoder memory (N_in, D) for session sid."""
        sess = self.sessions[sid]
        sess.queue.append(np.asarray(memory))
        sess.t_queue.append(time.perf_counter())

    # ---- jitted forward ----------------------------------------------------
    def _fwd_impl(self, params, memory, v, staged, pix2slot, keep_idx,
                  scale):
        from repro.msda.cache import MSDAValueCache
        from repro.msda.decoder import decoder_apply
        cache = MSDAValueCache(
            v=v, pix2slot=pix2slot, keep_idx=keep_idx,
            n_rows=self.mgr._n_rows, slot_windows=self.mgr._slot_windows,
            table_bytes=self.mgr._full_bytes, staged=staged, scale=scale)
        hs, refs, dstate = decoder_apply(
            params["decoder"], self.dec_cfg, self.plan, memory,
            collect_stats=self._update_fwp, cache=cache)
        cls_logits = nn.linear(params["cls_head"], hs)
        raw = nn.linear(params["box_head"], hs)
        cxy = jax.nn.sigmoid(raw[..., :2] + nn.inverse_sigmoid(refs))
        boxes = jnp.concatenate([cxy, jax.nn.sigmoid(raw[..., 2:])], axis=-1)
        freq = None
        if self._update_fwp:
            freq = sum(s["freq"] for s in dstate.collected_stats())
        return cls_logits, boxes, freq

    # ---- one engine step ---------------------------------------------------
    def step(self) -> int:
        """Ingest one pending frame per session; returns frames served."""
        pending = {s.slot: s for s in self.sessions.values() if s.queue}
        if not pending:
            return 0
        t_step0 = time.perf_counter()
        tr = self.obs.tracer
        d = self.attn_cfg.d_model
        with tr.span("frame_in", n=len(pending)) as _:
            rows = []
            for slot in range(self.max_sessions):
                if slot in pending:
                    rows.append(jnp.asarray(pending[slot].queue.popleft()))
                    pending[slot].t_queue.popleft()
                elif self._last_memory is not None:
                    # idle slot: replay its last memory — zero dirty
                    # tiles, zero incremental work attributed to it
                    rows.append(self._last_memory[slot])
                else:
                    rows.append(jnp.zeros((self.plan.n_in, d)))
            memory = jnp.stack(rows)
        self._last_memory = memory
        cache, fstats = self.mgr.step(memory)
        dec_span = tr.start("decode", n=len(pending))
        cls_logits, boxes, freq = self._fwd(
            self.params, memory, cache.v, cache.staged, cache.pix2slot,
            cache.keep_idx, cache.scale)
        if freq is not None:
            self.mgr.observe(freq)
        probs = np.asarray(jax.nn.softmax(cls_logits, axis=-1))
        boxes = np.asarray(boxes)
        if dec_span:
            sp = tr.end(dec_span)    # after np.asarray: compute included
            self._m_span.observe(sp.duration_s, span="decode")
        self._m_frame_latency.observe(time.perf_counter() - t_step0)
        for slot, sess in pending.items():
            sess.results.append({
                "frame": sess.frames_done,
                "cls_probs": probs[slot], "boxes": boxes[slot],
                "stream": fstats,
            })
            sess.frames_done += 1
            # the session's reference-point cluster: mean predicted box
            # center, normalized [0,1]^2 — reorder_sessions() sorts on it
            self._slot_centroid[slot] = boxes[slot][:, :2].mean(axis=0)
        return len(pending)

    # ---- cache-local session placement -------------------------------------
    def reorder_sessions(self, method: Optional[str] = None) -> dict:
        """Assign sessions whose reference points cluster to ADJACENT
        batch slots.

        The batched manager stores every per-slot array with batch as the
        leading axis, so slot adjacency IS memory adjacency: sessions
        looking at nearby image regions stage overlapping value-table
        rows, and placing them next to each other keeps those rows
        resident across the batch sweep. Sort key is the session centroid
        (mean predicted box center of its last frame) through the same
        :func:`repro.msda.ordering.query_sort_keys` the query paths use —
        ``method`` defaults to the plan's ``query_order`` (falling back
        to raster). Free slots are fixed points, so ``_free_slots`` stays
        valid; detections are per-slot state and move with their session,
        so results are unchanged. Returns {sid: slot} after the move."""
        from repro.msda import ordering
        if method is None:
            method = self.plan.query_order \
                if self.plan.query_order != "none" else "raster"
        sessions = sorted(self.sessions.values(), key=lambda s: s.sid)
        placed = [s for s in sessions if s.slot in self._slot_centroid]
        if len(placed) > 1:
            cents = jnp.asarray(
                np.stack([self._slot_centroid[s.slot] for s in placed]))
            keys = np.asarray(ordering.query_sort_keys(
                cents[None], self.plan.level_shapes, method))[0]
            order = np.argsort(keys, kind="stable")
            slots_sorted = sorted(s.slot for s in placed)
            perm = list(range(self.max_sessions))
            for i, j in enumerate(order):
                # key-sorted session i lands in the i-th occupied slot;
                # gather semantics: new slot takes the state at perm[slot]
                perm[slots_sorted[i]] = placed[int(j)].slot
            self.mgr.permute_slots(tuple(perm))
            if self._last_memory is not None:
                self._last_memory = jnp.take(
                    self._last_memory, jnp.asarray(perm), axis=0)
            old_cent = dict(self._slot_centroid)
            old_by_slot = {s.slot: s for s in placed}
            self._slot_centroid = {
                new: old_cent[old] for new, old in enumerate(perm)
                if old in old_cent}
            for new, old in enumerate(perm):
                if old in old_by_slot:
                    old_by_slot[old].slot = new
        return {s.sid: s.slot for s in self.sessions.values()}

    def run_until_drained(self, max_steps: int = 10000) -> None:
        steps = 0
        while any(s.queue for s in self.sessions.values()) \
                and steps < max_steps:
            if self.step() == 0:
                break
            steps += 1
        queued = {s.sid: len(s.queue)
                  for s in self.sessions.values() if s.queue}
        if queued:
            now = time.perf_counter()
            raise StarvationError({
                "engine": "StreamingDetrEngine", "steps": steps,
                "queued": queued,
                # per-session age of the oldest queued frame (same
                # perf_counter timeline the frame spans use)
                "oldest_age_s": {s.sid: round(now - s.t_queue[0], 6)
                                 for s in self.sessions.values()
                                 if s.t_queue},
                "frames_done": sum(s.frames_done
                                   for s in self.sessions.values())})
        self.obs.flush_metrics()

    def report(self) -> dict:
        """The manager's cumulative rebuild-vs-incremental accounting."""
        return self.mgr.report()
