"""Deformable-DETR-style decoder over ONE shared MSDAValueCache.

The paper's decoder workload is exactly where feature-map reusing pays:
few learned queries (N_q ≈ 300), many layers (6), one fixed memory (the
encoder output). Rebuilding the value table per layer — project, FWP-
compact, stage — costs ``n_layers``× the staged bytes for zero new
information. This decoder builds the cache ONCE
(:func:`repro.msda.cache.build_value_cache`, inheriting the encoder
chain's final FWP compaction) and every layer samples it through
:func:`repro.msda.attention.msda_attention_cached`:

    layer l:  self-attention over the N_q queries
              deformable cross-attention against the SHARED cache
              FFN
              reference-point refinement  ref <- sigmoid(logit(ref) + Δ(h))

The per-layer cross-attention owns its sampling weights (attention
logits, offsets, output projection) but NOT a value projection — that is
the build-once seam. The launch is decode-shaped: ``make_plan(...,
n_queries=N_q, n_consumers=n_layers)`` clamps the query tiling to the
learned-query regime and keeps ``auto`` off the raster-only windowed
kernel.

With the persistent decode backend (``pallas_decode``, the ``auto``
pick when the compact table fits the staging budget) the build-once
seam extends from projection to *staging*: ``build_value_cache`` lays
the table out in the decode launch layout exactly once per memory
(``cache.staged``) and every layer's launch reuses it — one staging per
(batch, head-group) per memory, not per layer. The layers still launch
one at a time (layer l's sampling coordinates only exist after layer
l-1's self-attn/FFN), which is why the stacked single-launch variant in
kernels/msgs_decode.py is reserved for coords-precomputed workloads;
the interleaved forward ships the per-layer persistent launches.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import nn
from repro.msda.attention import msda_attention_cached
from repro.msda.cache import build_value_cache
from repro.msda.pipeline import MSDAPipelineState
from repro.msda.plan import MSDAPlan


@dataclasses.dataclass(frozen=True)
class MSDADecoderConfig:
    """Static decoder shape. The attention geometry (d_model, heads,
    levels, DEFA knobs) comes from the plan's MSDeformAttnConfig — the
    decoder samples the SAME memory the encoder produced."""
    n_layers: int = 6
    n_queries: int = 300
    d_ffn: int = 1024
    dtype: Any = jnp.float32


def _cross_attn_init(key: jax.Array, attn_cfg) -> dict:
    """Per-layer deformable cross-attention params — the sampling weights
    WITHOUT a value projection (the shared cache owns that)."""
    from repro.core.msdeform_attn import init_msdeform_attn
    p = init_msdeform_attn(key, attn_cfg)
    return {k: v for k, v in p.items() if k not in ("value_w", "value_b")}


def init_decoder(key: jax.Array, cfg: MSDADecoderConfig, attn_cfg) -> dict:
    from repro.core.msdeform_attn import init_msdeform_attn
    d = attn_cfg.d_model
    key, kq, kt, kr, kv = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(jnp.asarray(float(d)))
    shared = init_msdeform_attn(kv, attn_cfg)
    params = {
        "query_pos": (jax.random.normal(kq, (cfg.n_queries, d))
                      * scale).astype(cfg.dtype),
        "tgt_embed": (jax.random.normal(kt, (cfg.n_queries, d))
                      * scale).astype(cfg.dtype),
        "ref_head": nn.linear_init(kr, d, 2, cfg.dtype),
        # the build-once seam: ONE value projection for all layers
        "value": {k: shared[k] for k in ("value_w", "value_b")},
        "layers": [],
    }
    for _ in range(cfg.n_layers):
        key, k1, k2, k3, k4, k5, k6, k7, k8 = jax.random.split(key, 9)
        params["layers"].append({
            "self_q": nn.linear_init(k1, d, d, cfg.dtype),
            "self_k": nn.linear_init(k2, d, d, cfg.dtype),
            "self_v": nn.linear_init(k3, d, d, cfg.dtype),
            "self_o": nn.linear_init(k4, d, d, cfg.dtype),
            "ln_sa": nn.layer_norm_init(d, cfg.dtype),
            "cross": _cross_attn_init(k5, attn_cfg),
            "ln1": nn.layer_norm_init(d, cfg.dtype),
            "ffn1": nn.linear_init(k6, d, cfg.d_ffn, cfg.dtype),
            "ffn2": nn.linear_init(k7, cfg.d_ffn, d, cfg.dtype),
            "ln2": nn.layer_norm_init(d, cfg.dtype),
            # zero-init refinement: layer 0 starts at the ref_head points
            "ref_delta": {
                "w": jnp.zeros((d, 2), cfg.dtype),
                "b": jnp.zeros((2,), cfg.dtype)},
        })
    return params


def decoder_logical_axes(cfg: MSDADecoderConfig) -> dict:
    lin = {"w": ("embed", None), "b": (None,)}
    ln = {"scale": (None,), "bias": (None,)}
    layer = {
        "self_q": lin, "self_k": lin, "self_v": lin, "self_o": lin,
        "ln_sa": ln,
        "cross": {"attn_w": ("embed", "heads", None), "attn_b": ("heads", None),
                  "offs_w": ("embed", "heads", None), "offs_b": ("heads", None),
                  "out_w": ("heads", None, "embed"), "out_b": (None,)},
        "ln1": ln, "ffn1": {"w": ("embed", "mlp"), "b": ("mlp",)},
        "ffn2": {"w": ("mlp", "embed"), "b": (None,)}, "ln2": ln,
        "ref_delta": lin,
    }
    return {
        "query_pos": (None, "embed"), "tgt_embed": (None, "embed"),
        "ref_head": lin,
        "value": {"value_w": ("embed", "heads", None), "value_b": ("heads", None)},
        "layers": [layer for _ in range(cfg.n_layers)],
    }


def _self_attention(layer: dict, h: jnp.ndarray, pos: jnp.ndarray,
                    n_heads: int) -> jnp.ndarray:
    """Standard MHA over the N_q queries (pos added to q/k, not v)."""
    b, n, d = h.shape
    dh = d // n_heads
    q = nn.linear(layer["self_q"], h + pos).reshape(b, n, n_heads, dh)
    k = nn.linear(layer["self_k"], h + pos).reshape(b, n, n_heads, dh)
    v = nn.linear(layer["self_v"], h).reshape(b, n, n_heads, dh)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
    att = jax.nn.softmax(att, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, n, d)
    return nn.linear(layer["self_o"], out)


@jax.named_scope("decoder")
def decoder_apply(
    params: dict,
    cfg: MSDADecoderConfig,
    plan: MSDAPlan,
    memory: jnp.ndarray,                    # (B, N_in, D) encoder output
    state: Optional[MSDAPipelineState] = None,
    *,
    collect_stats: bool = False,
    cache=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, MSDAPipelineState]:
    """Run the decoder stack against ONE shared value cache.

    ``state`` carries the encoder chain's final FWP link — its compaction
    decides the cache layout, so the decoder samples the same pruned
    table the last encoder block produced. ``cache`` lets a temporal
    consumer (the streaming engine) pass in a PERSISTENT, incrementally
    updated :class:`~repro.msda.cache.MSDAValueCache` instead of building
    one here — the frame-to-frame extension of the same build-once seam.
    Returns (h (B, N_q, D), refs (B, N_q, 2), decoder state). The
    returned state's ``block_stats`` has exactly one aligned entry per
    decoder layer and its ``cache`` is the shared table
    (``cache.table_bytes`` is the build-once staging cost every layer
    amortizes); a streaming caller's ``state.stream`` accounting is
    carried through."""
    b = memory.shape[0]
    attn_cfg = plan.cfg

    # ---- build ONCE: the shared, optionally FWP-compacted value table ----
    if cache is None:
        with jax.named_scope("cache_build"):
            cache = build_value_cache(params["value"], plan, memory, state)
    if plan.backend == "pallas_decode":
        # the persistent decode contract: the table was staged at build
        # time, once per memory — a missing staged block would silently
        # degrade every layer to a per-launch restage
        assert cache.staged is not None, \
            "pallas_decode plan produced an unstaged cache"
    dstate = MSDAPipelineState(
        fwp=getattr(state, "fwp", None),
        stream=getattr(state, "stream", None)).with_cache(cache)

    pos = params["query_pos"][None]                       # (1, Nq, D)
    h = jnp.broadcast_to(params["tgt_embed"][None],
                         (b,) + params["tgt_embed"].shape)
    refs = jax.nn.sigmoid(nn.linear(params["ref_head"], params["query_pos"]))
    refs = jnp.broadcast_to(refs[None], (b,) + refs.shape)  # (B, Nq, 2)

    for j, layer in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{j}"):
            with jax.named_scope("self_attn"):
                sa = _self_attention(layer, h, pos, attn_cfg.n_heads)
            with jax.named_scope("norm"):
                h = nn.layer_norm(layer["ln_sa"], h + sa)
            # ---- sample everywhere: cross-attention against the SHARED cache.
            # When the plan carries a query_order, the cached pass derives the
            # cache-local permutation PER LAYER from this layer's incoming
            # (pre-refinement) refs — the refinement below shifts every
            # layer's points, so no permutation survives across layers — and
            # inverts it on the output, so the ordering is invisible here.
            attn_out, dstate = msda_attention_cached(
                layer["cross"], plan, h + pos, refs, dstate.cache,
                state=dstate, collect_stats=collect_stats, update_fwp=False)
            with jax.named_scope("norm"):
                h = nn.layer_norm(layer["ln1"], h + attn_out)
            with jax.named_scope("ffn"):
                ff = nn.linear(layer["ffn2"],
                               jax.nn.relu(nn.linear(layer["ffn1"], h)))
            with jax.named_scope("norm"):
                h = nn.layer_norm(layer["ln2"], h + ff)
            # ---- per-layer reference-point refinement. The INCOMING refs are
            # detached (DETR-style truncated chain) but the delta itself is
            # live: its gradient flows through the later layers' sampling
            # locations and the final box head, which is what trains the
            # zero-initialized refinement weights.
            delta = h @ layer["ref_delta"]["w"] + layer["ref_delta"]["b"]
            refs = jax.nn.sigmoid(
                nn.inverse_sigmoid(jax.lax.stop_gradient(refs)) + delta)
    return h, refs, dstate
