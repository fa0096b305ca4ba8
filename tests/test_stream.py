"""Streaming temporal-reuse tests: tile geometry, incremental-vs-rebuild
parity (including the delta-threshold-0 mode across keep transitions),
frozen-scale quantization, staged-bytes accounting (the >= 2x
drifting-scene criterion), the staged-decode row scatter, and the
StreamingDetrEngine session lifecycle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import msda
from repro.core.msdeform_attn import MSDeformAttnConfig, init_msdeform_attn
from repro.msda.cache import build_value_cache
from repro.msda.pipeline import MSDAPipelineState
from repro.stream import (StreamConfig, TemporalCacheManager, drifting_scene,
                          tile_geometry)

LEVELS = ((8, 10), (4, 5), (2, 3))
N_IN = sum(h * w for h, w in LEVELS)
D = 32


def _cfg(**kw):
    base = dict(d_model=D, n_heads=4, n_levels=len(LEVELS), fwp_mode="compact",
                fwp_k=1.0, fwp_capacity=0.6, range_narrow=(4.0, 3.0, 2.0))
    base.update(kw)
    return MSDeformAttnConfig(**base)


def _mgr(cfg, scfg, batch=2, backend="jnp_gather", n_queries=16):
    params = init_msdeform_attn(jax.random.PRNGKey(0), cfg)
    plan = msda.make_plan(cfg, LEVELS, backend=backend,
                          n_queries=n_queries, n_consumers=2)
    vparams = {k: params[k] for k in ("value_w", "value_b")}
    return TemporalCacheManager(plan, vparams, scfg, batch=batch), plan


def _frames(key, batch=2, n=4):
    base = jax.random.normal(key, (batch, N_IN, D))
    return [base + 0.1 * t * jnp.sign(base) for t in range(n)]


def _scratch(mgr, plan, x):
    """Reference: a from-scratch build under the manager's CURRENT keep
    geometry — what a non-streaming deployment would rebuild per frame."""
    return build_value_cache(mgr.params, plan, jnp.asarray(x),
                             MSDAPipelineState(fwp=mgr.fwp))


# --------------------------------------------------------------------------
# tile geometry
# --------------------------------------------------------------------------

def test_tile_geometry_row_aligned_partition():
    geo = tile_geometry(LEVELS, tile_rows=2)
    # tiles partition the flat pixel space, in raster order
    assert geo.n_in == N_IN
    covered = np.zeros(N_IN, bool)
    for t in range(geo.n_tiles):
        lo = geo.tile_pix_start[t]
        hi = lo + geo.tile_pix_count[t]
        assert not covered[lo:hi].any()
        covered[lo:hi] = True
        np.testing.assert_array_equal(geo.tile_of_pixel[lo:hi], t)
        # row alignment: tile extent is a whole number of level rows
        w = LEVELS[geo.tile_level[t]][1]
        assert geo.tile_pix_count[t] % w == 0
    assert covered.all()
    with pytest.raises(ValueError):
        tile_geometry(LEVELS, tile_rows=0)


# --------------------------------------------------------------------------
# incremental parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fwp_mode,backend", [
    ("compact", "jnp_gather"), ("off", "jnp_gather"),
    ("mask", "jnp_gather"), ("compact", "pallas_decode")])
def test_incremental_tile_update_matches_scratch_build(fwp_mode, backend):
    """A localized feature change is scatter-updated into the persistent
    table (and its decode staging) EXACTLY as a from-scratch rebuild of
    the new memory would produce it."""
    cfg = _cfg(fwp_mode=fwp_mode)
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                       update_frac=0.5), backend=backend)
    key = jax.random.PRNGKey(1)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    x1 = x0.at[:, 3:6].add(0.5)                  # one tile of level 0
    cache, st = mgr.step(x1)
    assert st["mode"] == "incremental", st
    assert st["n_dirty"] > 0
    ref = _scratch(mgr, plan, x1)
    np.testing.assert_array_equal(np.asarray(cache.v), np.asarray(ref.v))
    if backend == "pallas_decode":
        assert cache.staged is not None
        np.testing.assert_array_equal(np.asarray(cache.staged.v),
                                      np.asarray(ref.staged.v))


def test_threshold0_parity_across_frames_with_keep_transition():
    """THE acceptance parity: delta-threshold 0 marks every tile changed,
    and across >= 3 consecutive frames — including a keep-mask
    transition — the incremental path's caches match a full per-frame
    rebuild within 1e-5."""
    cfg = _cfg()
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=0.0,
                                       update_frac=1.0))
    key = jax.random.PRNGKey(2)
    frames = _frames(key, n=5)
    # structured frequencies whose EMA will flip the warm-start keep set
    freq = jnp.where(jax.random.uniform(jax.random.fold_in(key, 9),
                                        (2, N_IN)) > 0.5, 10.0, 0.0)
    modes, transitions = [], 0
    for t, x in enumerate(frames):
        cache, st = mgr.step(x)
        modes.append(st["mode"])
        transitions += st["keep_transition"]
        ref = _scratch(mgr, plan, x)
        np.testing.assert_allclose(np.asarray(cache.v), np.asarray(ref.v),
                                   atol=1e-5)
        mgr.observe(freq)
    assert transitions >= 1, modes         # the keep set DID transition
    assert modes.count("incremental") >= 3, modes
    # all tiles really were marked changed on the incremental frames
    assert mgr.last_stats["mode"] == "incremental"


def test_over_budget_dirt_falls_back_to_rebuild():
    cfg = _cfg()
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                       update_frac=0.05))
    key = jax.random.PRNGKey(3)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    x1 = x0 + 1.0                                 # everything changes
    cache, st = mgr.step(x1)
    assert st["mode"] == "rebuild" and st["reason"] == "dirty>budget"
    ref = _scratch(mgr, plan, x1)
    np.testing.assert_array_equal(np.asarray(cache.v), np.asarray(ref.v))


def test_subthreshold_drift_accumulates_against_last_projection():
    """The diff reference is the memory as of each tile's last
    re-projection, so repeated sub-threshold drift eventually crosses the
    threshold instead of escaping detection forever."""
    cfg = _cfg(fwp_mode="off")
    thr = 0.5
    mgr, _ = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=thr,
                                    update_frac=1.0))
    key = jax.random.PRNGKey(4)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    x1 = x0.at[:, 0:3].add(0.3 * thr)             # below threshold
    _, st1 = mgr.step(x1)
    assert st1["mode"] == "incremental" and st1["n_dirty"] == 0
    x2 = x0.at[:, 0:3].add(1.2 * thr)             # cumulative drift crosses
    _, st2 = mgr.step(x2)
    assert st2["n_dirty"] > 0, st2


def test_frozen_scale_quant_keeps_table_grid_stable():
    """With INT12 activations on, incremental updates quantize against
    the scale captured at the last full build: re-projecting unchanged
    rows reproduces the table bit-for-bit (no grid drift)."""
    cfg = _cfg(act_bits=12, weight_bits=12)
    mgr, _ = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=0.0,
                                    update_frac=1.0))
    key = jax.random.PRNGKey(5)
    x0 = jax.random.normal(key, (2, N_IN, D))
    cache0, _ = mgr.step(x0)
    v0 = np.asarray(cache0.v)
    cache1, st = mgr.step(x0)                     # same memory, all "dirty"
    assert st["mode"] == "incremental"
    np.testing.assert_array_equal(np.asarray(cache1.v), v0)


def test_probed_diff_detects_full_width_changes():
    """Channel-strided diffing still catches a real tile change (the
    drifting scene perturbs every channel), and the parity contract is
    unchanged for the rows it updates."""
    cfg = _cfg()
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                       update_frac=0.5,
                                       diff_channel_stride=4))
    key = jax.random.PRNGKey(6)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    x1 = x0.at[:, 3:6].add(0.5)
    cache, st = mgr.step(x1)
    assert st["mode"] == "incremental" and st["n_dirty"] > 0
    ref = _scratch(mgr, plan, x1)
    np.testing.assert_array_equal(np.asarray(cache.v), np.asarray(ref.v))


def test_update_staged_rows_matches_full_restage():
    """Scattering a row subset into the staged decode layout equals
    re-staging the updated table from scratch."""
    from repro.kernels.msgs_decode import (stage_decode_table,
                                           update_staged_rows)
    key = jax.random.PRNGKey(7)
    b, n_rows, h, dh, u = 2, 11, 4, 8, 5
    v = jax.random.normal(key, (b, n_rows, h, dh))
    staged = stage_decode_table(v, head_pack=2)
    idx = jnp.stack([jnp.asarray([0, 3, 4, 7, 10]),
                     jnp.asarray([1, 2, 5, 8, 9])])
    rows = jax.random.normal(jax.random.fold_in(key, 1), (b, u, h, dh))
    bidx = jnp.arange(b)[:, None]
    v2 = v.at[bidx, idx].set(rows)
    got = update_staged_rows(staged, idx, rows)
    want = stage_decode_table(v2, head_pack=2)
    np.testing.assert_array_equal(np.asarray(got.v), np.asarray(want.v))


# --------------------------------------------------------------------------
# staged-bytes accounting — the >= 2x drifting-scene criterion
# --------------------------------------------------------------------------

def test_drifting_scene_bytes_ratio_at_least_2x():
    """The acceptance criterion: on the drifting-scene benchmark the
    incremental updates project/stage >= 2x fewer bytes than per-frame
    rebuilds (same measured path benchmarks/fmap_reuse.py reports)."""
    from benchmarks.fmap_reuse import _stream_staged
    r = _stream_staged(n_frames=32)
    assert r["stream_bytes_ratio"] >= 2.0, r
    assert r["stream_incremental_frames"] > r["stream_rebuild_frames"], r


def test_frame_stats_and_pipeline_state_carry_stream_accounting():
    cfg = _cfg()
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                       update_frac=0.5))
    key = jax.random.PRNGKey(8)
    x0 = jax.random.normal(key, (2, N_IN, D))
    _, st = mgr.step(x0)
    assert st["mode"] == "rebuild"
    assert st["staged_bytes"] == st["rebuild_bytes"] == mgr._full_bytes
    _, st = mgr.step(x0.at[:, 0:3].add(0.5))
    assert st["mode"] == "incremental"
    assert st["staged_bytes"] == plan.table_bytes_for_rows(
        mgr.update_rows, with_indirection=False)
    state = mgr.pipeline_state()
    assert state.stream is st and state.fwp is mgr.fwp
    # advance() preserves the frame accounting for every layer's consumer
    assert state.advance(None, None).stream is st
    r = mgr.report()
    assert r["frames"] == 2 and r["rebuild_frames"] == 1
    assert r["staged_bytes_total"] == st["staged_bytes"] + mgr._full_bytes
    # the plan's describe() surfaces the temporal accounting
    plan_s = dataclasses.replace(plan, stream_update_rows=mgr.update_rows)
    assert "stream<=" in plan_s.describe()


# --------------------------------------------------------------------------
# decoder + engine
# --------------------------------------------------------------------------

def _decoder_setup(backend="jnp_gather"):
    cfg = _cfg()
    dec_cfg = msda.MSDADecoderConfig(n_layers=2, n_queries=8, d_ffn=32)
    key = jax.random.PRNGKey(11)
    params = {
        "decoder": msda.init_decoder(key, dec_cfg, cfg),
        "cls_head": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                            (D, 3)) * 0.1,
                     "b": jnp.zeros((3,))},
        "box_head": {"w": jax.random.normal(jax.random.fold_in(key, 2),
                                            (D, 4)) * 0.1,
                     "b": jnp.zeros((4,))},
    }
    return cfg, dec_cfg, params


def test_decoder_apply_accepts_external_cache():
    """decoder_apply(cache=...) must run the stack against the provided
    cache and match the internally built one for identical memory."""
    cfg, dec_cfg, params = _decoder_setup()
    plan = msda.make_plan(cfg, LEVELS, backend="jnp_gather",
                          n_queries=dec_cfg.n_queries,
                          n_consumers=dec_cfg.n_layers)
    key = jax.random.PRNGKey(12)
    memory = jax.random.normal(key, (2, N_IN, D))
    h_int, refs_int, _ = msda.decoder_apply(params["decoder"], dec_cfg,
                                            plan, memory)
    cache = build_value_cache(params["decoder"]["value"], plan, memory)
    h_ext, refs_ext, dstate = msda.decoder_apply(
        params["decoder"], dec_cfg, plan, memory, cache=cache)
    np.testing.assert_allclose(np.asarray(h_int), np.asarray(h_ext),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(refs_int), np.asarray(refs_ext),
                               atol=1e-6)
    assert dstate.cache is cache


def test_streaming_engine_sessions_end_to_end():
    from repro.serve.engine import StreamingDetrEngine
    cfg, dec_cfg, params = _decoder_setup()
    engine = StreamingDetrEngine(
        cfg, dec_cfg, params, LEVELS, max_sessions=2,
        stream_cfg=StreamConfig(tile_rows=1, delta_threshold=1e-4,
                                update_frac=0.5))
    assert "streaming" in engine.describe()
    s0 = engine.open_session()
    s1 = engine.open_session()
    scenes = {s0: drifting_scene(1, LEVELS, D, 4),
              s1: drifting_scene(2, LEVELS, D, 4)}
    for t in range(4):
        for sid in (s0, s1):
            engine.submit_frame(sid, scenes[sid][t][0])
    engine.run_until_drained()
    for sid in (s0, s1):
        sess = engine.close_session(sid)
        assert len(sess.results) == 4
        for res in sess.results:
            assert res["cls_probs"].shape == (dec_cfg.n_queries, 3)
            assert res["boxes"].shape == (dec_cfg.n_queries, 4)
            assert np.isfinite(res["boxes"]).all()
            assert res["stream"]["mode"] in ("rebuild", "incremental",
                                             "partial")
    r = engine.report()
    assert r["frames"] == 4
    assert r["staged_bytes_total"] <= r["rebuild_bytes_total"]
    # freed slots are reusable
    s2 = engine.open_session()
    assert engine.sessions[s2].slot in (0, 1)


# --------------------------------------------------------------------------
# per-level partial restage + slot permutation (cache-local ordering)
# --------------------------------------------------------------------------

def _single_level_transition(mgr, key):
    """Drive the EMA so the keep set flips ONLY inside level 0."""
    freq = jnp.ones((2, N_IN))
    h0w0 = LEVELS[0][0] * LEVELS[0][1]
    flip = jnp.where(jax.random.uniform(key, (2, h0w0)) > 0.5, 10.0, 0.0)
    mgr.observe(freq.at[:, :h0w0].set(flip))


@pytest.mark.parametrize("backend", ("jnp_gather", "pallas_decode"))
def test_partial_restage_matches_scratch_build(backend):
    """A keep transition confined to one level restages ONLY that level's
    contiguous slot range (mode ``partial``), and the resulting cache —
    values, staged decode table AND swapped geometry — is bit-identical
    to a from-scratch build of the frame under the new keep set."""
    cfg = _cfg()
    # a small update budget, so the restage plus the incremental update
    # stage fewer bytes than a rebuild; the frame moves only level 0's
    # pixels, so the other levels stay clean and the result is exact
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-3,
                                       update_frac=0.25), backend=backend)
    key = jax.random.PRNGKey(31)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    _single_level_transition(mgr, jax.random.fold_in(key, 1))
    assert mgr._geometry_stale
    assert mgr._transition_levels() == (0,)
    h0w0 = LEVELS[0][0] * LEVELS[0][1]
    x1 = x0.at[:, :h0w0].add(0.05 * jnp.sign(x0[:, :h0w0]))
    cache, st = mgr.step(x1)
    assert st["mode"] == "partial" and st["reason"] == "keep-transition"
    assert st["restaged_levels"] == (0,)
    ref = _scratch(mgr, plan, x1)
    np.testing.assert_array_equal(np.asarray(cache.v), np.asarray(ref.v))
    np.testing.assert_array_equal(np.asarray(cache.keep_idx),
                                  np.asarray(ref.keep_idx))
    np.testing.assert_array_equal(np.asarray(cache.pix2slot),
                                  np.asarray(ref.pix2slot))
    if backend == "pallas_decode":
        np.testing.assert_array_equal(np.asarray(cache.staged.v),
                                      np.asarray(ref.staged.v))
        np.testing.assert_array_equal(np.asarray(cache.staged.remap),
                                      np.asarray(ref.staged.remap))
    assert mgr.report()["partial_frames"] == 1
    # accounting: the partial frame staged level 0's slots + the
    # incremental budget, not the whole table's indirection
    assert st["staged_bytes"] == plan.table_bytes_for_rows(
        mgr._slot_offs[1], with_indirection=False) \
        + LEVELS[0][0] * LEVELS[0][1] * 4 + mgr._incr_bytes


def test_partial_restage_declines_when_rebuild_is_cheaper():
    """A one-level transition whose restage plus the incremental update
    would stage at least a full rebuild's bytes rebuilds instead, so a
    stream never stages more than rebuilding every frame would."""
    cfg = _cfg()
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=0.0,
                                       update_frac=1.0))
    key = jax.random.PRNGKey(31)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    _single_level_transition(mgr, jax.random.fold_in(key, 1))
    assert mgr._geometry_stale
    assert mgr._partial_bytes((0,)) + mgr._incr_bytes >= mgr._full_bytes
    assert mgr._transition_levels() is None
    cache, st = mgr.step(x0 + 0.05 * jnp.sign(x0))
    assert st["mode"] == "rebuild" and st["reason"] == "keep-transition"
    assert st["staged_bytes"] == mgr._full_bytes
    assert mgr.report()["partial_frames"] == 0


def test_whole_geometry_transition_still_rebuilds():
    """When EVERY level's keep set moves, the partial path declines and
    the frame full-rebuilds (same bytes, one build)."""
    cfg = _cfg()
    mgr, _ = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=0.0,
                                    update_frac=1.0))
    key = jax.random.PRNGKey(32)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    flip = jnp.where(jax.random.uniform(jax.random.fold_in(key, 1),
                                        (2, N_IN)) > 0.5, 10.0, 0.0)
    mgr.observe(flip)
    assert mgr._geometry_stale
    assert mgr._transition_levels() is None
    _, st = mgr.step(x0)
    assert st["mode"] == "rebuild" and st["reason"] == "keep-transition"


def test_permute_slots_is_state_permutation():
    """permute_slots + step(permuted frames) == step(frames) + permute:
    the manager's per-slot state is exchangeable, which is what lets the
    engine place clustering sessions on adjacent slots without touching
    numerics."""
    cfg = _cfg()
    mk = lambda: _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                        update_frac=0.5),
                      backend="pallas_decode")[0]
    key = jax.random.PRNGKey(33)
    x0 = jax.random.normal(key, (2, N_IN, D))
    x1 = x0.at[:, 3:6].add(0.5)
    m_a = mk()
    m_a.step(x0)
    c_a, st_a = m_a.step(x1)
    m_b = mk()
    m_b.step(x0)
    m_b.permute_slots((1, 0))
    c_b, st_b = m_b.step(x1[::-1])
    assert st_a["mode"] == st_b["mode"] == "incremental"
    np.testing.assert_array_equal(np.asarray(c_b.v), np.asarray(c_a.v)[::-1])
    np.testing.assert_array_equal(np.asarray(c_b.staged.v),
                                  np.asarray(c_a.staged.v)[::-1])
    np.testing.assert_array_equal(np.asarray(m_b.x_ref),
                                  np.asarray(m_a.x_ref)[::-1])
    with pytest.raises(ValueError):
        m_b.permute_slots((0, 0))                  # not a permutation
    with pytest.raises(ValueError):
        m_b.permute_slots((0, 1, 2))               # wrong batch


def test_engine_reorder_sessions_never_drops_or_duplicates():
    """reorder_sessions() reassigns sessions to adjacent slots by
    reference-point cluster: the session set and the slot multiset are
    preserved, free slots stay free, and every session keeps serving its
    own stream afterwards."""
    from repro.serve.engine import StreamingDetrEngine
    cfg, dec_cfg, params = _decoder_setup()
    engine = StreamingDetrEngine(
        cfg, dec_cfg, params, LEVELS, max_sessions=3,
        stream_cfg=StreamConfig(tile_rows=1, delta_threshold=1e-4,
                                update_frac=0.5))
    sids = [engine.open_session() for _ in range(3)]
    scenes = {sid: drifting_scene(i + 1, LEVELS, D, 3)
              for i, sid in enumerate(sids)}
    for t in range(2):
        for sid in sids:
            engine.submit_frame(sid, scenes[sid][t][0])
    engine.run_until_drained()
    before = {s.sid: s.slot for s in engine.sessions.values()}
    mapping = engine.reorder_sessions()
    assert set(mapping) == set(before)                       # no session
    #   dropped or invented
    assert sorted(mapping.values()) == sorted(before.values())  # slots
    #   conserved (free slots stay free)
    # slot bookkeeping agrees between sessions dict and mapping
    for sid, slot in mapping.items():
        assert engine.sessions[sid].slot == slot
    # sessions keep serving their own streams post-reorder
    for sid in sids:
        engine.submit_frame(sid, scenes[sid][2][0])
    assert engine.step() == 3
    for sid in sids:
        sess = engine.sessions[sid]
        assert len(sess.results) == 3
        assert np.isfinite(sess.results[-1]["boxes"]).all()
    # closing a moved session frees its CURRENT slot for reuse
    freed = engine.close_session(sids[0]).slot
    s_new = engine.open_session()
    assert engine.sessions[s_new].slot == freed


def test_engine_reorder_noop_cases():
    """Reordering with < 2 placed sessions (or before any frame produced
    a centroid) is the identity."""
    from repro.serve.engine import StreamingDetrEngine
    cfg, dec_cfg, params = _decoder_setup()
    engine = StreamingDetrEngine(cfg, dec_cfg, params, LEVELS,
                                 max_sessions=2)
    assert engine.reorder_sessions() == {}
    s0 = engine.open_session()
    assert engine.reorder_sessions() == {s0: engine.sessions[s0].slot}


# --------------------------------------------------------------------------
# int8 table streaming: frozen scale, dtype guards, mid-stream plan swap
# --------------------------------------------------------------------------

def test_int8_stream_stays_int8_end_to_end():
    """A quantized-table stream never materializes a float table: the
    first-frame rebuild builds codes + frozen per-channel scale, and
    every incremental update scatters int8 codes into BOTH the cache
    table and its staged decode layout under the SAME scale (identical
    frame => bit-stable codes)."""
    cfg = _cfg(table_dtype="int8")
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                       update_frac=0.5),
                     backend="pallas_decode")
    assert plan.quantized_table
    key = jax.random.PRNGKey(21)
    x0 = jax.random.normal(key, (2, N_IN, D))
    cache0, st0 = mgr.step(x0)
    assert st0["mode"] == "rebuild"
    assert cache0.v.dtype == jnp.int8
    assert cache0.scale is not None and cache0.scale.dtype == jnp.float32
    assert cache0.staged is not None and cache0.staged.v.dtype == jnp.int8
    s0 = np.asarray(cache0.scale)
    cache1, st1 = mgr.step(x0.at[:, 3:6].add(0.5))
    assert st1["mode"] == "incremental" and st1["n_dirty"] > 0
    assert cache1.v.dtype == jnp.int8
    assert cache1.staged.v.dtype == jnp.int8
    # the scale is FROZEN for the cache's lifetime — updates requantize
    # onto the same grid, they never re-derive it
    np.testing.assert_array_equal(np.asarray(cache1.scale), s0)
    # identical frame: the requantized rows land on identical codes
    cache2, st2 = mgr.step(x0.at[:, 3:6].add(0.5))
    assert st2["mode"] == "incremental"
    np.testing.assert_array_equal(np.asarray(cache2.v), np.asarray(cache1.v))
    assert mgr.report()["table_dtype"] == "int8"


def test_int8_scatter_and_staged_update_reject_dtype_drift():
    """The hard guards behind the end-to-end int8 contract: scattering
    float rows into an int8 table (cache OR staged layout) raises instead
    of silently casting garbage onto the code grid."""
    from repro.kernels.msgs_decode import (stage_decode_table,
                                           update_staged_rows)
    from repro.msda.cache import scatter_table_rows
    cfg = _cfg(table_dtype="int8")
    mgr, _ = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                    update_frac=0.5),
                  backend="pallas_decode")
    key = jax.random.PRNGKey(22)
    cache, _ = mgr.step(jax.random.normal(key, (2, N_IN, D)))
    idx = jnp.zeros((2, 1), jnp.int32)
    f32_rows = jnp.zeros((2, 1) + cache.v.shape[2:], jnp.float32)
    with pytest.raises(TypeError, match="frozen scale"):
        scatter_table_rows(cache.v, idx, f32_rows)
    with pytest.raises(TypeError, match="dtype"):
        update_staged_rows(cache.staged, idx, f32_rows)
    # int8 codes (the quantize-then-scatter path) are accepted
    codes = jnp.zeros_like(f32_rows, jnp.int8)
    assert scatter_table_rows(cache.v, idx, codes).dtype == jnp.int8
    assert update_staged_rows(cache.staged, idx, codes).v.dtype == jnp.int8


def test_mid_stream_plan_swap_forces_full_rebuild():
    """Changing the manager's plan mid-stream (e.g. flipping the table
    dtype f32 -> int8) must force ONE full rebuild that re-derives the
    new layout + scale, then return to steady incremental updates."""
    cfg = _cfg()
    mgr, plan = _mgr(cfg, StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                       update_frac=0.5))
    key = jax.random.PRNGKey(23)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)
    cache, st = mgr.step(x0.at[:, 0:3].add(0.5))
    assert st["mode"] == "incremental"
    assert cache.scale is None and cache.v.dtype != jnp.int8
    plan8 = msda.make_plan(dataclasses.replace(cfg, table_dtype="int8"),
                           LEVELS, backend="jnp_gather", n_queries=16,
                           n_consumers=2)
    mgr.plan = plan8
    cache, st = mgr.step(x0.at[:, 0:3].add(0.5))
    assert st["mode"] == "rebuild" and st["reason"] == "plan-change", st
    assert cache.v.dtype == jnp.int8 and cache.scale is not None
    assert mgr.report()["table_dtype"] == "int8"
    # steady state resumes on the new plan — and stays int8
    cache, st = mgr.step(x0.at[:, 0:3].add(0.7))
    assert st["mode"] == "incremental"
    assert cache.v.dtype == jnp.int8


def test_streaming_engine_admission_is_slot_local():
    """Admitting a session mid-stream rebuilds ONLY the joining slot's
    rows — a batch-1 build scattered into the slot — while the running
    session rides the ordinary incremental path (no batch-wide rebuild
    storm), the admitted slot's table exactly matches a from-scratch
    build of its own frame (no stale-slot leakage), and repeated churn
    never retraces any compiled path."""
    from repro.core import fwp as fwp_lib
    from repro.serve.engine import StreamingDetrEngine
    cfg, dec_cfg, params = _decoder_setup()
    engine = StreamingDetrEngine(
        cfg, dec_cfg, params, LEVELS, max_sessions=2,
        stream_cfg=StreamConfig(tile_rows=1, delta_threshold=1e-4,
                                update_frac=0.9),
        update_fwp=False)     # freeze the keep set: isolates admission
    #   from warm-up EMA transitions
    mgr = engine.mgr
    s0 = engine.open_session()
    scene = drifting_scene(3, LEVELS, D, 3)
    engine.submit_frame(s0, scene[0][0])
    engine.step()
    engine.submit_frame(s0, scene[1][0])
    engine.step()
    assert mgr.last_stats["mode"] == "incremental"
    s1 = engine.open_session()                     # mid-stream admission
    engine.submit_frame(s0, scene[2][0])
    engine.submit_frame(s1, scene[0][0])
    engine.step()
    st = mgr.last_stats
    assert st["mode"] == "incremental", st         # no rebuild storm
    assert st["admitted_slots"] == (1,), st
    assert mgr.rebuild_frames == 1                 # only the first frame
    # the admitted slot's rows == a from-scratch build of its own frame
    # under its slot's keep geometry
    f = mgr.fwp
    fwp1 = None if f is None else fwp_lib.FWPState(
        keep_mask=f.keep_mask[1:2],
        keep_idx=None if f.keep_idx is None else f.keep_idx[1:2],
        pix2slot=None if f.pix2slot is None else f.pix2slot[1:2],
        freq=f.freq[1:2])
    ref = build_value_cache(mgr.params, mgr.plan,
                            jnp.asarray(scene[0][0])[None],
                            MSDAPipelineState(fwp=fwp1))
    np.testing.assert_allclose(np.asarray(mgr.cache.v[1]),
                               np.asarray(ref.v[0]), atol=1e-5)
    with pytest.raises(RuntimeError):
        engine.open_session()                      # only 2 slots
    # churn again: close + rejoin retraces NOTHING (the batch-1 build was
    # traced by the first admission) and stays slot-local
    traces = dict(mgr.trace_counts)
    engine.close_session(s1)
    s2 = engine.open_session()
    engine.submit_frame(s2, scene[1][0])
    engine.step()
    assert mgr.trace_counts == traces, (mgr.trace_counts, traces)
    assert mgr.last_stats["admitted_slots"] == (1,)
    assert mgr.last_stats["mode"] == "incremental"
    assert mgr.rebuild_frames == 1