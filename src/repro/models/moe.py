"""FFN layers: dense SwiGLU/GELU and Mixture-of-Experts.

MoE is SPMD-safe by construction: the token dispatch (top-k, sort, capacity
bucketing) happens PER DATA SHARD inside a shard_map, so no sort or scatter
ever crosses devices; tensor-parallel expert GEMMs keep partial sums in the
sharded hidden dimension and defer the all-reduce until after the
combine/segment-sum (one (tokens, d_model) psum per layer — identical wire
cost to a dense Megatron FFN, NOT inflated by expert capacity).

GShard's (tokens, E, capacity) one-hot dispatch einsum is deliberately
avoided: at assigned shapes it is O(10^13) elements. A jit-global argsort is
also avoided: GSPMD would all-gather the token stream.

Weight layout note: gate/up projections are stored (d, 2, f) — NEVER fused
(d, 2f) — so TP-sharding f never splits across the gate/up boundary.

Shapes: x (B, S, D) with B sharded over the data axes; everything else local.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import (ParamSpec, Tree, sanitized_pspecs,
                                 tree_pspecs)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Runtime sharding context threaded through model applies.

    None ctx (tests / single device) runs the same math without collectives.
    """
    mesh: Any
    dp: tuple[str, ...]          # data axes, e.g. ("pod", "data")
    tp: str = "model"
    rules: Any = None            # logical-axis -> mesh-axis mapping
    sp_residual: bool = False    # Megatron-SP: residual stream sharded on
                                 # seq over the model axis (AG/RS instead of
                                 # AR around each block — halves TP wire)
    residual_spec: Any = None    # explicit P(...) pinned on the residual
                                 # stream between blocks (zero3 needs this —
                                 # GSPMD otherwise drops the batch sharding
                                 # inside attention and replicates 256x)


# ---------------------------------------------------------------------------
# dense FFN


def swiglu_spec(d: int, f: int) -> Tree:
    return {
        "wi": ParamSpec((d, 2, f), ("embed", "null", "mlp")),
        "wo": ParamSpec((f, d), ("mlp", "embed")),
    }


def swiglu(p: Tree, x):
    u = jnp.einsum("...d,dcf->...cf", x, p["wi"])
    return (jax.nn.silu(u[..., 0, :]) * u[..., 1, :]) @ p["wo"]


def gelu_mlp_spec(d: int, f: int) -> Tree:
    return {
        "wi": ParamSpec((d, f), ("embed", "mlp")),
        "bi": ParamSpec((f,), ("mlp",), init="zeros", dtype=jnp.float32),
        "wo": ParamSpec((f, d), ("mlp", "embed")),
        "bo": ParamSpec((d,), ("embed",), init="zeros", dtype=jnp.float32),
    }


def gelu_mlp(p: Tree, x):
    h = jax.nn.gelu(x @ p["wi"] + p["bi"].astype(x.dtype))
    return h @ p["wo"] + p["bo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE


def moe_spec(cfg) -> Tree:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    s: Tree = {
        "router": ParamSpec((d, e), ("embed", "null"), dtype=jnp.float32),
        "wi": ParamSpec((e, d, 2, f), ("experts", "embed", "null", "mlp")),
        "wo": ParamSpec((e, f, d), ("experts", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        s["shared"] = swiglu_spec(d, f * cfg.n_shared_experts)
    return s


def _dispatch_indices(expert_ids, capacity: int):
    """expert_ids: (N,) int32. Returns (slot (N,), keep (N,)) — slot is the
    entry's rank within its expert (sorted-segment prefix trick, local)."""
    n = expert_ids.shape[0]
    order = jnp.argsort(expert_ids, stable=True)
    sorted_e = expert_ids[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool),
                                sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(is_start, pos, 0))
    rank_sorted = pos - seg_start
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    return rank, rank < capacity


def _moe_local(cfg, p: Tree, x, ctx: ShardCtx | None, *,
               tp_axis=None, ep_axis=None, batch_axes=None):
    """Per-data-shard MoE. Two weight-parallel modes share the code path:

      TP  (tp_axis): every expert's hidden dim f is sharded; expert GEMM
          outputs are partial over f.
      EP  (ep_axis): the EXPERT bank is sharded (e_loc = E/P experts per
          device); tokens are replicated along that axis, so each device
          computes only the tokens routed to ITS experts and contributes
          zero for the rest — no all_to_all needed on this mesh (tokens are
          dp-sharded on other axes). Full-width per-expert GEMMs: much
          better MXU shapes than TP's f/P slivers (olmoe: f=1024 vs 64).

    Either way the result is combined with ONE deferred psum of (tokens, d)
    after the combine — identical wire cost to a dense Megatron FFN."""
    b, s, d = x.shape
    e, k, f_cfg = cfg.n_experts, cfg.top_k, cfg.d_ff
    n = b * s
    xt = x.reshape(n, d)

    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    gates, eids = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(gates, axis=-1)

    flat_e = eids.reshape(-1).astype(jnp.int32)
    if n <= 1024:               # decode-sized shard: dropless
        capacity = n
    else:
        capacity = int(cfg.moe_capacity_factor * n * k / e) + 1
    slot, keep = _dispatch_indices(flat_e, capacity)

    e_loc = p["wi"].shape[0]                                  # E or E/P (EP)
    local_e = flat_e
    if ep_axis is not None and e_loc != e:
        lo = jax.lax.axis_index(ep_axis) * e_loc
        owner = (flat_e >= lo) & (flat_e < lo + e_loc)
        keep = keep & owner
        local_e = flat_e - lo

    flat_tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    target = jnp.where(keep, local_e * capacity + slot, e_loc * capacity)
    buckets = jnp.zeros((e_loc * capacity + 1, d), x.dtype)
    buckets = buckets.at[target].set(xt[flat_tok])
    buckets = buckets[:-1].reshape(e_loc, capacity, d)

    u = jnp.einsum("ecd,edgf->ecgf", buckets, p["wi"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(u[..., 0, :]) * u[..., 1, :],
                   p["wo"])                # partial over f (TP) / owner (EP)

    y_flat = y.reshape(e_loc * capacity, d)
    gathered = jnp.where(keep[:, None],
                         y_flat[jnp.minimum(target, e_loc * capacity - 1)], 0)
    wk = weights.reshape(-1)[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(gathered * wk, flat_tok, num_segments=n)

    psum_axis = tp_axis or ep_axis
    if cfg.n_shared_experts:
        shared = swiglu(p["shared"], xt)
        if ep_axis is not None and psum_axis is not None:
            # shared experts are replicated under EP: pre-scale so the
            # combining psum over the axis is exact
            shared = shared / jax.lax.psum(
                jnp.ones((), shared.dtype), psum_axis)
        out = out + shared

    aux = _aux_loss(logits, flat_e, keep & (slot >= 0), e)
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)                    # deferred sum
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return out.reshape(b, s, d), aux


def _aux_loss(logits, flat_e, keep, e: int):
    probs = jax.nn.softmax(logits, axis=-1)
    me = probs.mean(axis=0)
    counts = jax.ops.segment_sum(keep.astype(jnp.float32), flat_e,
                                 num_segments=e)
    ce = counts / jnp.maximum(counts.sum(), 1.0)
    return e * jnp.sum(me * ce)


def moe_ffn(cfg, p: Tree, x, ctx: ShardCtx | None):
    """Public MoE entry: shard_map'd when a sharding ctx is present.

    The local math supports only hidden-dim (mlp) weight sharding; any other
    weight sharding the layout prescribes (e.g. zero3's embed-dim shards) is
    all-gathered at the shard_map boundary — which IS the ZeRO-3 per-layer
    weight gather."""
    if ctx is None:
        return _moe_local(cfg, p, x, None)
    rules = dict(ctx.rules or {})
    mlp_axis = rules.get("mlp")
    ep_axis = rules.get("experts")
    if ep_axis is not None and cfg.n_experts % ctx.mesh.shape.get(ep_axis, 1):
        ep_axis = None                      # uneven expert split: fall back
    if isinstance(ep_axis, tuple):
        ep_axis = None
    if ep_axis is not None and ep_axis == mlp_axis:
        mlp_axis = None                     # EP takes the axis; shared/dense
                                            # FFN outside moe_ffn keeps TP
    moe_rules = {k: None for k in rules}
    moe_rules["mlp"] = mlp_axis
    moe_rules["experts"] = ep_axis
    pspecs = sanitized_pspecs(moe_spec(cfg), moe_rules, ctx.mesh)
    batch_axes = rules.get("batch", ctx.dp)
    bt = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
    bt = tuple(a for a in bt if a)
    xspec = P(batch_axes, None, None)

    def inner(p_, x_):
        return _moe_local(cfg, p_, x_, ctx, tp_axis=mlp_axis, ep_axis=ep_axis,
                          batch_axes=bt)

    return jax.shard_map(
        inner, mesh=ctx.mesh,
        in_specs=(pspecs, xspec),
        out_specs=(xspec, P()), check_vma=False,
    )(p, x)
