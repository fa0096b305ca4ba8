"""Oracle parity + planning tests for the repro.msda subsystem.

Every registered backend must produce the same numbers as the pure
per-level oracle (``msdeform_attn_ref``) when pruning is off (or covers
everything), and must agree with the ``jnp_gather`` backend under real
PAP-topk / FWP-compact pruning. Plan auto-selection and the head-packed
(4 heads x Dh=32 -> 128 lanes) dispatch are exercised explicitly."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import msda
from repro.core import nn
from repro.core.msdeform_attn import (
    MSDeformAttnConfig, init_msdeform_attn, msdeform_attn_ref)

LEVELS = ((16, 20), (8, 10), (4, 5), (2, 3))
N_IN = sum(h * w for h, w in LEVELS)
B, D = 1, 64
RANGES = (6.0, 4.0, 3.0, 2.0)
# raster-query backends (pallas_decode is decode-shaped only: its parity
# matrix lives in the "persistent decode" section below)
ALL_BACKENDS = ("jnp_gather", "pallas_fused", "pallas_windowed")


@pytest.fixture(scope="module")
def setup():
    # Raster-ordered encoder queries (pallas_windowed needs Nq == N_in)
    cfg = MSDeformAttnConfig(d_model=D, n_heads=2, range_narrow=RANGES)
    key = jax.random.PRNGKey(0)
    params = init_msdeform_attn(key, cfg)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, N_IN, D))
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, N_IN, D))
    refs = jnp.broadcast_to(
        nn.reference_points_for_levels(LEVELS)[None], (B, N_IN, 2))
    out_ref = msdeform_attn_ref(params, cfg, q, refs, x, LEVELS)
    return cfg, params, q, refs, x, out_ref


def _run(setup_t, backend, state=None, **cfg_kw):
    cfg, params, q, refs, x, _ = setup_t
    cfg2 = dataclasses.replace(cfg, **cfg_kw)
    plan = msda.make_plan(cfg2, LEVELS, backend=backend, block_q=64)
    return msda.msda_attention(params, plan, q, refs, x, state=state)


# --------------------------------------------------------------------------
# oracle parity — all backends vs. the independent per-level reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_matches_oracle_plain(setup, backend):
    out, _ = _run(setup, backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(setup[-1]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_matches_oracle_pap_topk_covering(setup, backend):
    """PAP-topk keeping every point must still equal the oracle exactly."""
    cfg = setup[0]
    out, _ = _run(setup, backend, pap_mode="topk", pap_keep=cfg.n_lp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(setup[-1]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_matches_oracle_fwp_compact_covering(setup, backend):
    """FWP-compact with full capacity & zero threshold keeps every pixel:
    the compacted execution must reproduce the oracle bit-for-tolerance."""
    _, st1 = _run(setup, "jnp_gather", fwp_mode="compact", fwp_k=0.0,
                  fwp_capacity=1.0)
    out, _ = _run(setup, backend, state=st1, fwp_mode="compact", fwp_k=0.0,
                  fwp_capacity=1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(setup[-1]),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# cross-backend parity under REAL pruning (output != oracle by design)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("pallas_fused", "pallas_windowed"))
def test_backend_matches_jnp_pap_topk(setup, backend):
    kw = dict(pap_mode="topk", pap_keep=8)
    want, _ = _run(setup, "jnp_gather", **kw)
    out, _ = _run(setup, backend, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ("pallas_fused", "pallas_windowed"))
def test_backend_matches_jnp_fwp_compact(setup, backend):
    kw = dict(fwp_mode="compact", fwp_k=1.0, fwp_capacity=0.6)
    _, st1 = _run(setup, "jnp_gather", **kw)
    want, _ = _run(setup, "jnp_gather", state=st1, **kw)
    out, _ = _run(setup, backend, state=st1, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ("pallas_fused", "pallas_windowed"))
def test_backend_matches_jnp_pap_and_fwp_combined(setup, backend):
    kw = dict(pap_mode="topk", pap_keep=8,
              fwp_mode="compact", fwp_k=1.0, fwp_capacity=0.6)
    _, st1 = _run(setup, "jnp_gather", **kw)
    want, _ = _run(setup, "jnp_gather", state=st1, **kw)
    out, _ = _run(setup, backend, state=st1, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# multi-scale-parallel windowed kernel: full pruning/layout matrix
# --------------------------------------------------------------------------

def _combo_setup(packed: bool):
    """Geometry pair: packed (8 heads x Dh=32 -> 4-head lane groups) vs
    genuinely unpacked (Dh=40 does not divide 128 -> pad layout, G=1)."""
    d, heads = (256, 8) if packed else (80, 2)
    cfg = MSDeformAttnConfig(d_model=d, n_heads=heads, range_narrow=RANGES)
    key = jax.random.PRNGKey(11 if packed else 13)
    params = init_msdeform_attn(key, cfg)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, N_IN, d))
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, N_IN, d))
    refs = jnp.broadcast_to(
        nn.reference_points_for_levels(LEVELS)[None], (B, N_IN, 2))
    return cfg, params, q, refs, x


@pytest.mark.parametrize("packed", (False, True), ids=("padlane", "packed"))
@pytest.mark.parametrize("pap", ("off", "topk"))
@pytest.mark.parametrize("fwp", ("off", "mask", "compact"))
def test_windowed_msp_matches_jnp_all_modes(fwp, pap, packed):
    """Single-launch windowed kernel vs the jnp_gather oracle under every
    combination of {FWP-compact, FWP-mask, PAP-topk, head-packed}."""
    cfg, params, q, refs, x = _combo_setup(packed)
    kw = {}
    if pap == "topk":
        kw.update(pap_mode="topk", pap_keep=8)
    if fwp != "off":
        kw.update(fwp_mode=fwp, fwp_k=1.0, fwp_capacity=0.6)
    cfg2 = dataclasses.replace(cfg, **kw)
    plan_j = msda.make_plan(cfg2, LEVELS, backend="jnp_gather", block_q=64)
    plan_w = msda.make_plan(cfg2, LEVELS, backend="pallas_windowed",
                            block_q=64)
    if packed:
        assert plan_w.lane_layout == "pack" and plan_w.head_pack == 4
    else:
        assert plan_w.lane_layout == "pad" and plan_w.head_pack == 1
    state = None
    if fwp != "off":            # block 1 builds the mask block 2 consumes
        _, state = msda.msda_attention(params, plan_j, q, refs, x)
        assert state.fwp is not None
    want, _ = msda.msda_attention(params, plan_j, q, refs, x, state=state)
    out, _ = msda.msda_attention(params, plan_w, q, refs, x, state=state)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


class _TakeAlongAxisSpy:
    """Records every jnp.take_along_axis call's operand rank."""
    def __init__(self):
        self.ndims = []
        self._real = jnp.take_along_axis

    def __call__(self, arr, idx, axis=None, **kwargs):
        self.ndims.append(arr.ndim)
        return self._real(arr, idx, axis=axis, **kwargs)


def _spy_densify(monkeypatch, setup_t, backend):
    cfg, params, q, refs, x, _ = setup_t
    kw = dict(fwp_mode="compact", fwp_k=1.0, fwp_capacity=0.6)
    _, st1 = _run(setup_t, "jnp_gather", **kw)
    spy = _TakeAlongAxisSpy()
    monkeypatch.setattr(jnp, "take_along_axis", spy)
    _run(setup_t, backend, state=st1, **kw)
    monkeypatch.undo()
    return spy


def test_windowed_msp_never_densifies_compact_table(setup, monkeypatch):
    """The single-launch windowed path must never materialize the
    densified (B, N_in, H, Dh) table: no take_along_axis on the 4-D
    value table is traced anywhere in the FWP-compact windowed execution
    (5-D calls are the per-point offset selection, 3-D the compact value
    projection — neither touches the staged table)."""
    spy = _spy_densify(monkeypatch, setup, "pallas_windowed")
    assert all(nd != 4 for nd in spy.ndims), spy.ndims


def test_densify_spy_positive_control(setup, monkeypatch):
    """The spy must catch a real backend that densifies, through the SAME
    execution path the negative tests use. (The old positive control was
    the retired pallas_windowed_loop backend; this registers a probe
    backend that densifies the compact table exactly as the loop did —
    pix2slot broadcast + 4-D take_along_axis — then gathers.)"""
    from repro.msda import backends as backend_registry

    @msda.register_backend("densify_probe")
    def densify_probe(plan, v, pts, probs, cache=None):
        if pts.pix2slot is not None:
            idx = pts.pix2slot[:, :, None, None]
            idx = jnp.broadcast_to(idx, (v.shape[0], plan.n_in) + v.shape[2:])
            v = jnp.take_along_axis(v, idx, axis=1)   # the densify the
            #   single-launch kernel exists to avoid
            pts = pts._replace(pix2slot=None, keep_idx=None)
        return backend_registry.jnp_gather(plan, v, pts, probs)

    try:
        spy = _spy_densify(monkeypatch, setup, "densify_probe")
    finally:
        backend_registry._REGISTRY.pop("densify_probe", None)
    assert any(nd == 4 for nd in spy.ndims), spy.ndims


# --------------------------------------------------------------------------
# persistent decode kernel: parity matrix, staging spy, gradients
# --------------------------------------------------------------------------

N_DEC_Q = 20


def _decode_setup(packed: bool, fwp: str):
    """Decode-shaped workload (N_q learned queries) with an optional
    encoder pass to build the FWP link the cache prunes by."""
    cfg, params, q, refs, x = _combo_setup(packed)
    if fwp != "off":
        cfg = dataclasses.replace(cfg, fwp_mode=fwp, fwp_k=1.0,
                                  fwp_capacity=0.6)
    key = jax.random.PRNGKey(17 if packed else 19)
    dq = jax.random.normal(key, (B, N_DEC_Q, cfg.d_model))
    drefs = jax.random.uniform(jax.random.fold_in(key, 1), (B, N_DEC_Q, 2),
                               minval=0.05, maxval=0.95)
    state = None
    if fwp != "off":
        plan_e = msda.make_plan(cfg, LEVELS, backend="jnp_gather")
        _, state = msda.msda_attention(params, plan_e, q, refs, x)
        assert state.fwp is not None
    return cfg, params, dq, drefs, x, state


@pytest.mark.parametrize("packed", (False, True), ids=("padlane", "packed"))
@pytest.mark.parametrize("fwp", ("off", "mask", "compact"))
def test_decode_backend_matches_jnp_all_modes(fwp, packed):
    """pallas_decode vs the jnp_gather oracle on decode-shaped launches
    across {FWP off/mask/compact} x {packed/pad-lane}."""
    cfg, params, dq, drefs, x, state = _decode_setup(packed, fwp)
    outs = {}
    for be in ("jnp_gather", "pallas_decode"):
        plan = msda.make_plan(cfg, LEVELS, backend=be, n_queries=N_DEC_Q,
                              n_consumers=6)
        if packed:
            assert plan.lane_layout == "pack" and plan.head_pack == 4
        else:
            assert plan.lane_layout == "pad" and plan.head_pack == 1
        out, _ = msda.msda_attention(params, plan, dq, drefs, x, state=state)
        outs[be] = np.asarray(out)
    np.testing.assert_allclose(outs["pallas_decode"], outs["jnp_gather"],
                               rtol=2e-5, atol=2e-5)


class _StagingSpy:
    """Counts calls of the once-per-memory decode staging op."""
    def __init__(self):
        self.calls = 0
        self.staged_shapes = []
        from repro.kernels import msgs_decode
        self._real = msgs_decode.stage_decode_table

    def __call__(self, *args, **kwargs):
        self.calls += 1
        out = self._real(*args, **kwargs)
        self.staged_shapes.append(tuple(out.v.shape))
        return out


def test_decode_stages_table_once_per_memory_not_per_layer(monkeypatch):
    """THE persistent-decode contract: a full 6-layer decode against one
    memory stages the compact table exactly ONCE — the single staged
    array covers every (batch, head-group) block — never once per
    layer."""
    from repro.kernels import msgs_decode
    cfg, params, _, _, x, state = _decode_setup(True, "compact")
    dcfg = msda.MSDADecoderConfig(n_layers=6, n_queries=N_DEC_Q, d_ffn=64)
    dparams = msda.init_decoder(jax.random.PRNGKey(23), dcfg, cfg)
    plan = msda.make_plan(cfg, LEVELS, backend="pallas_decode",
                          n_queries=dcfg.n_queries,
                          n_consumers=dcfg.n_layers)
    spy = _StagingSpy()
    monkeypatch.setattr(msgs_decode, "stage_decode_table", spy)
    h, _, dstate = msda.decoder_apply(dparams, dcfg, plan, x, state)
    monkeypatch.undo()
    assert spy.calls == 1, \
        f"table staged {spy.calls}x for {dcfg.n_layers} layers"
    # the ONE staging covers all (batch, head-group) blocks of the memory
    b, n_groups, n_rows, gdh = spy.staged_shapes[0]
    assert (b, n_groups) == (B, cfg.n_heads // plan.head_pack)
    assert n_rows == dstate.cache.n_rows
    assert gdh == plan.head_pack * cfg.head_dim
    assert dstate.cache.staged is not None
    assert len(dstate.block_stats) == dcfg.n_layers
    assert bool(jnp.all(jnp.isfinite(h)))


def test_decode_staging_spy_positive_control(monkeypatch):
    """The spy must catch per-layer restaging through the same execution
    path: sampling a cache built WITHOUT the staged block (a jnp_gather
    plan's cache) through pallas_decode pays the fallback staging on
    every layer — n_layers spy calls, which is exactly what the
    persistent path eliminates."""
    from repro.kernels import msgs_decode
    cfg, params, dq, drefs, x, state = _decode_setup(True, "compact")
    plan_j = msda.make_plan(cfg, LEVELS, backend="jnp_gather",
                            n_queries=N_DEC_Q)
    plan_d = msda.make_plan(cfg, LEVELS, backend="pallas_decode",
                            n_queries=N_DEC_Q)
    cache = msda.build_value_cache(params, plan_j, x, state)
    assert cache.staged is None
    spy = _StagingSpy()
    monkeypatch.setattr(msgs_decode, "stage_decode_table", spy)
    for _ in range(3):
        msda.msda_attention_cached(params, plan_d, dq, drefs, cache,
                                   state, update_fwp=False)
    monkeypatch.undo()
    assert spy.calls == 3, spy.calls


@pytest.mark.skipif(
    os.environ.get("REPRO_MSDA_TABLE_DTYPE") == "int8",
    reason="int8 tables round values onto the code grid: the value "
           "projection's gradient vanishes through round() by "
           "construction, so grad parity is a float-table contract")
def test_decode_grad_parity_through_full_decoder():
    """Gradient-parity smoke through the FULL 6-layer decode: the
    pallas_decode custom_vjp (backward = exact jnp reference) must
    produce the same loss and parameter gradients as the all-jnp oracle
    stack — the first trainable Pallas backend."""
    cfg, params, _, _, x, state = _decode_setup(False, "compact")
    dcfg = msda.MSDADecoderConfig(n_layers=6, n_queries=N_DEC_Q, d_ffn=64)
    dparams = msda.init_decoder(jax.random.PRNGKey(29), dcfg, cfg)

    def loss_for(backend):
        plan = msda.make_plan(cfg, LEVELS, backend=backend,
                              n_queries=dcfg.n_queries,
                              n_consumers=dcfg.n_layers)

        def loss(p):
            h, refs, _ = msda.decoder_apply(p, dcfg, plan, x, state)
            return jnp.mean(jnp.square(h)) + jnp.mean(refs)
        return jax.value_and_grad(loss)(dparams)

    val_j, grads_j = loss_for("jnp_gather")
    val_d, grads_d = loss_for("pallas_decode")
    np.testing.assert_allclose(float(val_d), float(val_j),
                               rtol=1e-4, atol=1e-5)
    flat_j = jax.tree.leaves(grads_j)
    flat_d = jax.tree.leaves(grads_d)
    assert len(flat_j) == len(flat_d)
    for gj, gd in zip(flat_j, flat_d):
        np.testing.assert_allclose(np.asarray(gd), np.asarray(gj),
                                   rtol=1e-3, atol=1e-4)
    # the shared value projection receives gradient through the STAGED
    # table's custom_vjp (transpose-aware backward)
    assert float(np.abs(np.asarray(grads_d["value"]["value_w"])).sum()) > 0


# --------------------------------------------------------------------------
# int8 value table: full sampled-output parity vs the f32 pipeline
# --------------------------------------------------------------------------

INT8_PARITY_BACKENDS = ("jnp_gather", "pallas_fused", "pallas_decode",
                        "pallas_windowed")


@pytest.mark.parametrize("packed", (False, True), ids=("padlane", "packed"))
@pytest.mark.parametrize("fwp", ("off", "compact"))
@pytest.mark.parametrize("backend", INT8_PARITY_BACKENDS)
def test_int8_table_matches_f32_within_quant_tol(backend, fwp, packed):
    """END-TO-END int8 parity: the same geometry sampled through the int8
    table (codes + frozen per-channel scale, dequantized after the
    bilinear aggregation) must match the f32 pipeline within the ANALYTIC
    quantization bound — each code rounds by at most scale/2, and
    bilinear weights x attention probabilities form a convex combination,
    so per-element |err| <= scale/2 (+ float noise). Explicit
    ``table_dtype`` pins BOTH sides regardless of the
    REPRO_MSDA_TABLE_DTYPE env, so the matrix is identical on the CI int8
    leg. The FWP sentinel row must quantize to code 0 (pruned taps stay
    exactly zero)."""
    if backend == "pallas_decode":
        cfg, params, q2, refs2, x, state = _decode_setup(packed, fwp)
        plan_kw = dict(n_queries=N_DEC_Q, n_consumers=6)
    else:
        cfg, params, q2, refs2, x = _combo_setup(packed)
        state = None
        plan_kw = dict(block_q=64)
        if fwp == "compact":
            cfg = dataclasses.replace(cfg, fwp_mode="compact", fwp_k=1.0,
                                      fwp_capacity=0.6)
            plan_e = msda.make_plan(cfg, LEVELS, backend="jnp_gather",
                                    block_q=64)
            _, state = msda.msda_attention(params, plan_e, q2, refs2, x)
            assert state.fwp is not None

    cfg32 = dataclasses.replace(cfg, table_dtype="float32")
    cfg8 = dataclasses.replace(cfg, table_dtype="int8")
    plan32 = msda.make_plan(cfg32, LEVELS, backend="jnp_gather", **plan_kw)
    plan8 = msda.make_plan(cfg8, LEVELS, backend=backend, **plan_kw)
    assert plan8.quantized_table and not plan32.quantized_table
    want, _ = msda.msda_attention(params, plan32, q2, refs2, x, state=state)
    out, _ = msda.msda_attention(params, plan8, q2, refs2, x, state=state)

    # the scale the int8 run derived (deterministic per memory)
    cache8 = msda.build_value_cache(params, plan8, x, state)
    assert cache8.v.dtype == jnp.int8
    assert cache8.scale is not None
    # per-head sampled outputs are convex combinations of table rows, so
    # their error is <= scale/2 per (h, dh) channel; the output
    # projection then mixes channels: |err_d| <= sum_hk |W_o| * scale/2
    scale = np.asarray(cache8.scale, np.float64)      # (B, 1, H, Dh)
    w_abs = np.abs(np.asarray(params["out_w"], np.float64))   # (H, Dh, D)
    tol = np.einsum("bohk,hkd->bod", scale / 2, w_abs) + 2e-5  # (B, 1, D)
    err = np.abs(np.asarray(out, np.float64) - np.asarray(want, np.float64))
    assert np.all(err <= tol), \
        f"max excess {float((err - tol).max()):.3e} over analytic tol"
    if fwp == "compact":
        assert not np.any(np.asarray(cache8.v)[:, -1]), \
            "FWP sentinel row must be code 0 (exact zero)"


# --------------------------------------------------------------------------
# bf16 value table: every Pallas backend vs jnp_gather in bf16
# --------------------------------------------------------------------------

@pytest.mark.parametrize("packed", (False, True), ids=("padlane", "packed"))
@pytest.mark.parametrize("backend", ("pallas_fused", "pallas_windowed",
                                     "pallas_decode"))
def test_bf16_table_matches_jnp(backend, packed):
    """A bf16 model (bf16 table, queries and weights — the precision of
    ``configs/detr_family.py``) runs on every Pallas backend and agrees
    with ``jnp_gather`` on the same bf16 inputs. Both paths accumulate
    the corners in f32 and round the per-head samples to bf16 once, so
    they differ by about one bf16 rounding (2^-8 relative) through the
    bf16 output projection."""
    if backend == "pallas_decode":
        cfg, params, q2, refs2, x, _ = _decode_setup(packed, "off")
        plan_kw = dict(n_queries=N_DEC_Q, n_consumers=6)
    else:
        cfg, params, q2, refs2, x = _combo_setup(packed)
        plan_kw = dict(block_q=64)
    bf = jnp.bfloat16
    cfg = dataclasses.replace(cfg, dtype=bf, table_dtype="bfloat16")
    params = jax.tree.map(lambda a: a.astype(bf), params)
    q2, refs2, x = q2.astype(bf), refs2.astype(bf), x.astype(bf)
    outs = {}
    for be in ("jnp_gather", backend):
        plan = msda.make_plan(cfg, LEVELS, backend=be, **plan_kw)
        assert plan.table_dtype == "bfloat16"
        out, _ = msda.msda_attention(params, plan, q2, refs2, x)
        assert out.dtype == bf
        outs[be] = np.asarray(out.astype(jnp.float32))
    np.testing.assert_allclose(outs[backend], outs["jnp_gather"],
                               rtol=1e-2, atol=1e-2)


# --------------------------------------------------------------------------
# plan resolution
# --------------------------------------------------------------------------

def test_plan_auto_prefers_fused_when_table_fits(setup):
    plan = msda.make_plan(setup[0], LEVELS, backend="auto")
    assert plan.backend == "pallas_fused"
    assert plan.fits_vmem


def test_plan_auto_falls_to_windowed_when_table_exceeds_budget(setup):
    plan = msda.make_plan(setup[0], LEVELS, backend="auto",
                          vmem_budget_bytes=1024)   # table is ~213 KB
    assert plan.backend == "pallas_windowed"
    assert not plan.fits_vmem


def test_plan_auto_respects_query_count_hint(setup):
    """Decoder-style queries (Nq != N_in) can't use the windowed kernel:
    the hint keeps auto from planning a backend that must crash."""
    plan = msda.make_plan(setup[0], LEVELS, backend="auto",
                          vmem_budget_bytes=1024, n_queries=7)
    assert plan.backend == "jnp_gather"
    plan = msda.make_plan(setup[0], LEVELS, backend="auto",
                          vmem_budget_bytes=1024, n_queries=N_IN)
    assert plan.backend == "pallas_windowed"


def test_plan_auto_respects_window_staging_budget(setup, monkeypatch):
    """The auto policy consults the co-resident staged window sum against
    the REPRO_MSDA_VMEM_BUDGET staging budget: when the sum of the L
    level windows can't co-reside, the windowed kernel would blow VMEM,
    so auto must fall back to jnp_gather."""
    plan = msda.make_plan(setup[0], LEVELS, backend="auto",
                          vmem_budget_bytes=1024)
    assert plan.backend == "pallas_windowed"       # fits the default budget
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "1000")
    assert msda.window_staging_budget() == 1000
    plan = msda.make_plan(setup[0], LEVELS, backend="auto",
                          vmem_budget_bytes=1024)
    assert plan.backend == "jnp_gather"
    # block 1 of a compact chain has no FWP link yet and stages the DENSE
    # windows, so the gate must enforce the worst case: a budget between
    # the compact and dense sums is NOT enough for the windowed kernel
    cfg_c = dataclasses.replace(setup[0], fwp_mode="compact",
                                fwp_capacity=0.6)
    probe = msda.make_plan(cfg_c, LEVELS, backend="jnp_gather")
    assert probe.window_bytes_compact < probe.window_bytes
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET",
                       str(probe.window_bytes_compact))
    plan = msda.make_plan(cfg_c, LEVELS, backend="auto",
                          vmem_budget_bytes=1024)
    assert plan.backend == "jnp_gather"
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", str(probe.window_bytes))
    plan = msda.make_plan(cfg_c, LEVELS, backend="auto",
                          vmem_budget_bytes=1024)
    assert plan.backend == "pallas_windowed"


def test_vmem_budget_env_rejects_malformed_values(monkeypatch):
    """REPRO_MSDA_VMEM_BUDGET parsing is hardened: a malformed value
    raises a clear error naming the variable (not a bare int() traceback),
    non-positive values are rejected, and valid decimal/hex parse."""
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "4MB")
    with pytest.raises(ValueError, match="REPRO_MSDA_VMEM_BUDGET"):
        msda.window_staging_budget()
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "-4096")
    with pytest.raises(ValueError, match="positive"):
        msda.window_staging_budget()
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "0")
    with pytest.raises(ValueError, match="positive"):
        msda.window_staging_budget()
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "123456")
    assert msda.window_staging_budget() == 123456
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "0x100000")
    assert msda.window_staging_budget() == 1 << 20
    # zero-padded decimal stays decimal (no surprise octal/base-0 reject)
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "04194304")
    assert msda.window_staging_budget() == 4194304
    monkeypatch.delenv("REPRO_MSDA_VMEM_BUDGET")
    assert msda.window_staging_budget() == msda.DEFAULT_WINDOW_STAGING_BUDGET


def test_vmem_budget_env_parses_once_per_value(monkeypatch):
    """The parse is cached per observed raw string: a stable env is
    parsed once per process, while CHANGING the value mid-process still
    re-parses (plan_for keys its memo on the resolved budget, so no
    stale plan is served either way)."""
    from repro.msda.plan import _parse_budget_env
    _parse_budget_env.cache_clear()
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "777216")
    assert msda.window_staging_budget() == 777216
    misses = _parse_budget_env.cache_info().misses
    for _ in range(3):
        assert msda.window_staging_budget() == 777216
    info = _parse_budget_env.cache_info()
    assert info.misses == misses and info.hits >= 3
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "888832")
    assert msda.window_staging_budget() == 888832    # re-parsed, not stale
    assert _parse_budget_env.cache_info().misses == misses + 1


def test_plan_decode_shaped_tiling(setup):
    """N_q learned queries are a different block_q regime: the tile clamps
    to next_pow2(N_q), the windowed kernel is rejected, and describe()
    surfaces the build-once cache accounting."""
    plan = msda.make_plan(setup[0], LEVELS, backend="jnp_gather",
                          n_queries=40, n_consumers=6)
    assert plan.decode_shaped
    assert plan.block_q == 64                      # next_pow2(40), not 128
    assert plan.tile_q == 64
    assert plan.window_bytes is None               # no raster windows
    assert "q=decode(40)" in plan.describe()
    assert "build-once" in plan.describe()
    with pytest.raises(ValueError):
        msda.make_plan(setup[0], LEVELS, backend="pallas_windowed",
                       n_queries=40)
    # raster query count hint is NOT decode-shaped
    plan = msda.make_plan(setup[0], LEVELS, backend="jnp_gather",
                          n_queries=N_IN)
    assert not plan.decode_shaped


def test_plan_auto_selects_persistent_decode(setup, monkeypatch):
    """Decode-shaped auto prefers the persistent decode kernel when the
    once-staged table + one layer's operands fit the staging budget
    (REPRO_MSDA_VMEM_BUDGET gate, extended with the decode operand
    accounting); degraded budgets fall back fused -> jnp."""
    cfg_c = dataclasses.replace(setup[0], fwp_mode="compact",
                                fwp_capacity=0.6)
    plan = msda.make_plan(cfg_c, LEVELS, backend="auto", n_queries=40,
                          n_consumers=6)
    assert plan.backend == "pallas_decode"
    assert plan.decode_operand_bytes is not None
    assert "staged=1x" in plan.describe()
    assert f"{plan.n_consumers}x table restage" in plan.describe()
    # a staging budget too small for table+operands rejects the decode
    # kernel; the whole-table slab still fits the default VMEM budget
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", "1000")
    plan = msda.make_plan(cfg_c, LEVELS, backend="auto", n_queries=40)
    assert plan.backend == "pallas_fused"
    # WORST-CASE rule: a decoder fed no FWP link stages the DENSE table
    # (build_value_cache's documented fallback), so a budget between the
    # compact and dense footprints must ALSO reject the decode kernel —
    # same argument as value_rows() and the windowed max(dense, compact)
    # dtype-aware: the dense fallback stages the table at the plan's
    # RESOLVED table dtype (int8 under REPRO_MSDA_TABLE_DTYPE=int8 stages
    # 1-byte codes + one f32 scale row, ~4x fewer bytes)
    dense = plan.table_bytes_for_rows(plan.n_in, with_indirection=False)
    assert plan.cache_table_bytes < dense
    monkeypatch.setenv("REPRO_MSDA_VMEM_BUDGET", str(dense - 1))
    plan = msda.make_plan(cfg_c, LEVELS, backend="auto", n_queries=40)
    assert plan.backend == "pallas_fused"
    # and with the VMEM slab gone too, the oracle path remains
    plan = msda.make_plan(cfg_c, LEVELS, backend="auto", n_queries=40,
                          vmem_budget_bytes=1024)
    assert plan.backend == "jnp_gather"


def test_plan_decode_only_backend_rejected_for_raster(setup):
    """pallas_decode needs a decode-shaped plan: raster launches (no
    n_queries, or n_queries == N_in) must be rejected at plan time."""
    with pytest.raises(ValueError):
        msda.make_plan(setup[0], LEVELS, backend="pallas_decode")
    with pytest.raises(ValueError):
        msda.make_plan(setup[0], LEVELS, backend="pallas_decode",
                       n_queries=N_IN)


def test_backend_registry_metadata():
    """The planner consults registry metadata, not name prefixes: the
    windowed kernel is raster-only, the decode kernel decode-only, and
    unregistered probes default to geometry-neutral."""
    assert msda.backend_info("pallas_windowed").raster_only
    assert not msda.backend_info("pallas_windowed").decode_only
    assert msda.backend_info("pallas_decode").decode_only
    assert not msda.backend_info("jnp_gather").raster_only
    assert msda.backend_info("never_registered") == msda.BackendInfo()


def test_plan_auto_falls_to_jnp_without_range_narrowing(setup):
    cfg = dataclasses.replace(setup[0], range_narrow=None)
    plan = msda.make_plan(cfg, LEVELS, backend="auto", vmem_budget_bytes=1024)
    assert plan.backend == "jnp_gather"


def test_plan_windowed_requires_range_narrowing(setup):
    cfg = dataclasses.replace(setup[0], range_narrow=None)
    with pytest.raises(ValueError):
        msda.make_plan(cfg, LEVELS, backend="pallas_windowed")


def test_plan_unknown_backend_rejected(setup):
    with pytest.raises(ValueError):
        msda.make_plan(setup[0], LEVELS, backend="nope")


def test_plan_block_q_clamped_per_level(setup):
    """min(block_q, next_pow2(nq_l)): the (2,3) level's 6 queries tile as
    8, not 128, and the (4,5) level's 20 queries as 32."""
    plan = msda.make_plan(setup[0], LEVELS, backend="jnp_gather", block_q=128)
    assert plan.block_q_levels == (128, 128, 32, 8)
    assert plan.tile_q == 128
    plan = msda.make_plan(setup[0], LEVELS, backend="jnp_gather", block_q=16)
    assert plan.block_q_levels == (16, 16, 16, 8)


def test_plan_describe_reports_window_accounting(setup):
    """The windowed kernel's staged-VMEM accounting shows up in describe:
    dense window always (range_narrow set), compact window when FWP
    compaction shrinks what is actually staged."""
    plan = msda.make_plan(setup[0], LEVELS, backend="pallas_windowed")
    assert plan.window_bytes is not None and plan.window_bytes > 0
    assert plan.window_bytes_compact is None
    assert "win=" in plan.describe()
    cfg2 = dataclasses.replace(setup[0], fwp_mode="compact",
                               fwp_capacity=0.6)
    plan2 = msda.make_plan(cfg2, LEVELS, backend="pallas_windowed")
    assert plan2.window_bytes_compact is not None
    assert plan2.window_bytes_compact < plan2.window_bytes
    assert "compact" in plan2.describe()


def test_plan_legacy_impl_mapping(setup):
    cfg = dataclasses.replace(setup[0], impl="pallas")
    assert msda.make_plan(cfg, LEVELS).backend == "pallas_fused"
    cfg = dataclasses.replace(setup[0], impl="jnp")
    assert msda.make_plan(cfg, LEVELS).backend == "jnp_gather"
    # explicit cfg.backend overrides impl
    cfg = dataclasses.replace(setup[0], impl="jnp", backend="pallas_fused")
    assert msda.make_plan(cfg, LEVELS).backend == "pallas_fused"


def test_registry_lists_all_builtins():
    for name in ALL_BACKENDS + ("pallas_decode",):
        assert name in msda.available_backends()
        assert callable(msda.get_backend(name))


# --------------------------------------------------------------------------
# head-packed lane layout (4 heads x Dh=32 -> one 128-lane group)
# --------------------------------------------------------------------------

def test_lane_layout_resolution():
    assert msda.lane_layout(8, 32) == ("pack", 4)     # 4x32 = 128 lanes
    assert msda.lane_layout(8, 128) == ("native", 1)
    assert msda.lane_layout(3, 40) == ("pad", 1)      # 40 doesn't divide 128


def test_head_packed_backend_matches_oracle():
    """DETR-scale head geometry (8 heads, Dh=32): the plan packs 4 heads
    per lane group and the packed kernel must equal the oracle."""
    cfg = MSDeformAttnConfig(d_model=256, n_heads=8, range_narrow=RANGES)
    key = jax.random.PRNGKey(3)
    params = init_msdeform_attn(key, cfg)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, N_IN, 256))
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, N_IN, 256))
    refs = jnp.broadcast_to(
        nn.reference_points_for_levels(LEVELS)[None], (B, N_IN, 2))
    plan = msda.make_plan(cfg, LEVELS, backend="pallas_fused", block_q=64)
    assert plan.lane_layout == "pack" and plan.head_pack == 4
    out, _ = msda.msda_attention(params, plan, q, refs, x)
    want = msdeform_attn_ref(params, cfg, q, refs, x, LEVELS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# pipeline state threading
# --------------------------------------------------------------------------

def test_pipeline_state_threads_fwp_chain(setup):
    cfg, params, q, refs, x, _ = setup
    cfg2 = dataclasses.replace(cfg, fwp_mode="compact", fwp_k=1.0,
                               fwp_capacity=0.8)
    plan = msda.make_plan(cfg2, LEVELS, backend="jnp_gather")
    state = msda.MSDAPipelineState.initial()
    assert state.fwp is None and state.block_index == 0
    _, state = msda.msda_attention(params, plan, q, refs, x, state=state,
                                   collect_stats=True)
    assert state.fwp is not None and state.block_index == 1
    assert len(state.block_stats) == 1
    assert "pap_keep_frac" in state.block_stats[0]
    _, state = msda.msda_attention(params, plan, q, refs, x, state=state,
                                   collect_stats=True)
    assert state.block_index == 2 and len(state.block_stats) == 2
    assert "fwp_keep_frac" in state.block_stats[1]


def test_pipeline_block_stats_stay_aligned_when_toggled(setup):
    """Toggling collect_stats mid-chain must NOT silently drop entries:
    block_stats[i] is block i's entry (None when it didn't collect), so
    indices track block_index exactly."""
    cfg, params, q, refs, x, _ = setup
    cfg2 = dataclasses.replace(cfg, fwp_mode="compact", fwp_k=1.0,
                               fwp_capacity=0.8)
    plan = msda.make_plan(cfg2, LEVELS, backend="jnp_gather")
    state = msda.MSDAPipelineState.initial()
    for collect in (False, True, False, True):
        _, state = msda.msda_attention(params, plan, q, refs, x,
                                       state=state, collect_stats=collect)
    assert state.block_index == 4
    assert len(state.block_stats) == 4             # aligned, not compacted
    assert state.block_stats[0] is None and state.block_stats[2] is None
    assert state.block_stats[1] is not None and state.block_stats[3] is not None
    # block 1 consumed block 0's FWP mask: its stats must say so
    assert int(state.block_stats[1]["value_rows"]) < N_IN
    assert state.collected_stats() == (state.block_stats[1],
                                       state.block_stats[3])
