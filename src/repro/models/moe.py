"""Mixture-of-Experts block with the datapath's angular mode as the router.

Paper integration (DESIGN.md §4): router scores between token activations
and expert embeddings are exactly the paper's **OpAngular** jobs — dot
products q·eᵢ, optionally normalized into full cosine similarity by the
"external divider" epilogue.  The router literally queries a session-API
``repro.core.session.VectorIndex`` over the expert embeddings, the same
code path validated against the datapath kernels.

Expert parallelism (EP): experts are sharded over the ``model`` mesh axis.
Tokens stay replicated across that axis (they already are — attention
output is TP-all-reduced to the full d_model), each shard computes *its*
experts' contributions via capacity-gather, and one ``psum`` over 'model'
combines.  This avoids the (tokens, E, capacity) one-hot dispatch tensor of
GShard-style einsum MoE — with E=256 (deepseek) that tensor is O(10^13)
elements; the capacity-gather form is O(tokens·E) for routing metadata and
O(E_local·C·d) for compute.  Implemented as a ``shard_map`` so the gather/
scatter stay shard-local instead of tripping GSPMD's gather partitioner.

Capacity: per-shard per-expert C = ceil(tokens_local · top_k / E · cf);
overflow tokens are dropped (GShard semantics; deviation from DeepSeek's
dropless balancing is recorded in DESIGN.md).  A load-balance aux loss
(Switch-style) is returned for training.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.session import VectorIndex
from .config import ModelConfig, MoEConfig
from .layers import dense_init, split


def moe_init(rng, cfg: ModelConfig):
    m: MoEConfig = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = split(rng, 6)
    p = {
        # router: expert embeddings — the OpAngular "candidate points"
        "router": dense_init(ks[0], (e, d), in_axis=1),
        "wi": dense_init(ks[1], (e, d, f), in_axis=1),
        "wg": dense_init(ks[2], (e, d, f), in_axis=1),
        "wo": dense_init(ks[3], (e, f, d), in_axis=1),
    }
    if m.num_shared:
        p["shared_wi"] = dense_init(ks[4], (d, f * m.num_shared))
        p["shared_wg"] = dense_init(ks[5], (d, f * m.num_shared))
        p["shared_wo"] = dense_init(
            jax.random.fold_in(rng, 9), (f * m.num_shared, d))
    return p


def router_scores(m: MoEConfig, x_flat: jax.Array, router_w: jax.Array):
    """Datapath OpAngular jobs: scores[n, e] = x_n · router_e (or cosine).

    The expert table is a session-API :class:`VectorIndex` (the OpAngular
    candidate points) built in-trace — its ``||e||^2`` norms are computed
    once and shared by the cosine epilogue instead of re-reduced per call.
    """
    index = VectorIndex.from_database(router_w.astype(jnp.float32))
    queries = x_flat.astype(jnp.float32)
    if m.router_metric == "cosine":
        return index.cosine_similarity(queries)
    return index.dots(queries)


def router_topk(m: MoEConfig, scores: jax.Array):
    """Top-k gating.  Returns (weights (N,k), experts (N,k), aux_loss)."""
    n, e = scores.shape
    if m.router == "sigmoid":  # deepseek-v3 gating
        probs = jax.nn.sigmoid(scores)
        w, idx = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20) * m.route_scale
        full = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-20)
    else:
        probs = jax.nn.softmax(scores, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        full = probs
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (N,k,E)
    f_e = onehot.sum((0, 1)) / jnp.maximum(n * m.top_k, 1)
    p_e = full.mean(0)
    aux = e * jnp.sum(f_e * p_e)
    return w.astype(jnp.float32), idx, aux


def _expert_ffn(cfg: ModelConfig, wi, wg, wo, xs):
    """xs (E_local, C, D) through per-expert gated MLP."""
    dt = xs.dtype
    h = jnp.einsum("ecd,edf->ecf", xs, wi.astype(dt))
    g = jnp.einsum("ecd,edf->ecf", xs, wg.astype(dt))
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))


def moe_local(cfg: ModelConfig, x_flat, weights, experts, wi, wg, wo,
              expert_offset: int, capacity: int):
    """Capacity-gather MoE over a *local* expert slice [offset, offset+E_loc).

    x_flat (N, D); weights/experts (N, k); expert weights (E_loc, D, F) etc.
    Returns (N, D) partial output — contributions of local experts only.
    """
    n, d = x_flat.shape
    e_loc = wi.shape[0]
    k = experts.shape[1]
    flat_e = experts.reshape(-1)  # (N*k,)
    flat_w = weights.reshape(-1)
    local = flat_e - expert_offset  # index into local slice
    in_range = (local >= 0) & (local < e_loc)
    local = jnp.where(in_range, local, 0)

    # slot position of each (token, choice) within its expert, via cumsum of
    # one-hot over local experts (N*k, E_loc) — the dispatch bookkeeping.
    onehot = jax.nn.one_hot(local, e_loc, dtype=jnp.int32) * in_range[:, None]
    pos = jnp.cumsum(onehot, axis=0) - 1  # position within expert
    slot = jnp.take_along_axis(pos, local[:, None], axis=1)[:, 0]
    keep = in_range & (slot < capacity)

    # scatter token ids into the (E_loc, C) dispatch table; -1 = empty
    table = jnp.full((e_loc, capacity), n, jnp.int32)  # n = padding token id
    gather_w = jnp.zeros((e_loc, capacity), jnp.float32)
    token_of = jnp.arange(n * k, dtype=jnp.int32) // k
    se = jnp.where(keep, local, e_loc)  # overflow -> dropped row
    ss = jnp.where(keep, slot, 0)
    table = table.at[se, ss].set(jnp.where(keep, token_of, n), mode="drop")
    gather_w = gather_w.at[se, ss].set(jnp.where(keep, flat_w, 0.0), mode="drop")

    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)], 0)
    xs = x_pad[table]  # (E_loc, C, D)
    ys = _expert_ffn(cfg, wi, wg, wo, xs)
    ys = ys * gather_w[..., None].astype(ys.dtype)

    # combine: scatter-add back over tokens
    out = jnp.zeros((n + 1, d), ys.dtype)
    out = out.at[table.reshape(-1)].add(ys.reshape(-1, d), mode="drop")
    return out[:n]


def moe_apply(cfg: ModelConfig, ctx, p, x):
    """Full MoE block.  x (B, T, D) -> (y (B, T, D), aux_loss)."""
    m: MoEConfig = cfg.moe
    b, t, d = x.shape
    x_flat = x.reshape(b * t, d)

    scores = router_scores(m, x_flat, p["router"])  # OpAngular jobs
    weights, experts, aux = router_topk(m, scores)

    ep = (ctx.mesh is not None and ctx.model_axis is not None
          and m.num_experts % ctx.model_size == 0 and ctx.model_size > 1)
    if ep:
        y = _moe_ep(cfg, ctx, p, x_flat, weights, experts)
    else:
        n_loc = x_flat.shape[0]
        cap = _capacity(m, n_loc)
        y = moe_local(cfg, x_flat, weights, experts,
                      p["wi"], p["wg"], p["wo"], 0, cap)

    if m.num_shared:
        dt = x_flat.dtype
        h = x_flat @ p["shared_wi"].astype(dt)
        g = x_flat @ p["shared_wg"].astype(dt)
        y = y + (jax.nn.silu(g) * h) @ p["shared_wo"].astype(dt)
    return y.reshape(b, t, d).astype(x.dtype), aux


def _capacity(m: MoEConfig, n_tokens: int) -> int:
    per = n_tokens * m.top_k / m.num_experts * m.capacity_factor
    return max(8, -(-int(per) // 8) * 8)


def _moe_ep(cfg: ModelConfig, ctx, p, x_flat, weights, experts):
    """Expert-parallel path: shard_map over (batch-axes × model axis)."""
    m: MoEConfig = cfg.moe
    mesh = ctx.mesh
    batch_axes = tuple(a for a in (
        (ctx.batch_axes if isinstance(ctx.batch_axes, tuple)
         else (ctx.batch_axes,))) if a in mesh.shape)
    model_axis = ctx.model_axis
    e_loc = m.num_experts // mesh.shape[model_axis]
    n_shards = 1
    for a in batch_axes:
        n_shards *= mesh.shape[a]
    # token axis must divide the data shards to shard it; tiny token counts
    # (e.g. single-token decode) fall back to replicated routing — every
    # shard routes all tokens over its local experts, psum still combines.
    if x_flat.shape[0] % n_shards != 0 or n_shards == 1:
        batch_axes = ()
        n_shards = 1
    n_local = x_flat.shape[0] // n_shards
    cap = _capacity(m, n_local)

    def shard_fn(xl, wl, el, wi, wg, wo):
        # local expert slice index along 'model'
        midx = jax.lax.axis_index(model_axis)
        offset = midx * e_loc
        y = moe_local(cfg, xl, wl, el, wi, wg, wo, offset, cap)
        # combine expert contributions living on other model shards;
        # combine_dtype='bfloat16' halves the dominant EP traffic
        cd = jnp.dtype(m.combine_dtype)
        return jax.lax.psum(y.astype(cd), model_axis)

    tok_spec = P(batch_axes if batch_axes else None, None)
    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=tok_spec,
    )(x_flat, weights, experts, p["wi"], p["wg"], p["wo"])
    return out


def count_moe_params(cfg: ModelConfig) -> tuple[int, int]:
    """(total, active-per-token) params of one MoE block (excl. router)."""
    m = cfg.moe
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    total = m.num_experts * per_expert + m.num_experts * cfg.d_model
    shared = m.num_shared * 3 * cfg.d_model * m.d_ff_expert
    active = m.top_k * per_expert + shared
    return total + shared, active
