"""Mixture-of-Experts block with expert parallelism.

Design (production path, DeepSeek/GShard-style with capacity):

  1. Router (GSPMD, replicated weights): softmax top-k + Switch-style
     load-balance aux loss, computed *outside* the manual region so the aux
     loss is an ordinary traced scalar.
  2. Dispatch (shard_map, manual over the whole mesh): tokens are sorted by
     expert id, packed into a (E, C, D) capacity buffer per chip, and
     exchanged with the expert owners over the 'model' axis via
     ``lax.all_to_all`` — the same token-dispatch / result-combine
     synchronization points §3.2 of the paper calls out as the MoE straggler
     amplifier.
  3. Expert FFN: grouped gated-MLP einsum over the local experts; expert
     weights arrive FSDP-sharded on d_model and are all-gathered over 'data'
     (ZeRO-3 style) just-in-time.
  4. Combine: inverse all_to_all, unsort, weighted sum over k.

Shared experts (always-on) run as a plain dense GSPMD FFN outside the manual
region and are added to the routed output.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import api as dist
from repro.models import common as cm
from repro.models.layers import apply_mlp, init_mlp


def init_moe(keys, cfg):
    m = cfg.moe
    d = cfg.d_model
    p = {
        "router": cm.dense(next(keys), d, m.num_experts, (None, None)),
        # stacked expert mats: (E, D, 2F) and (E, F, D). Master weights +
        # moments stay (expert x fsdp) 2-D sharded; the bf16 compute copy
        # is gathered over fsdp ONCE per layer pass by the model's ZeRO-3
        # JIT gather (dist.gather_fsdp) BEFORE the shard_map, so the manual
        # region sees (expert-sharded, replicated-d) weights with no
        # in-region all-gather (§Perf iteration 5)
        "wi": cm.Annot(
            jax.random.normal(next(keys), (m.num_experts, d, 2 * m.expert_d_ff),
                              jnp.float32) / math.sqrt(d),
            ("expert", "fsdp", None)),
        "wo": cm.Annot(
            jax.random.normal(next(keys), (m.num_experts, m.expert_d_ff, d),
                              jnp.float32) / math.sqrt(m.expert_d_ff),
            ("expert", "fsdp", None)),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(keys, d, m.num_shared_experts * m.expert_d_ff,
                               cfg.act)
    return p


def _route(router_w, x, num_experts: int, top_k: int):
    """Returns (weights (B,S,k) fp32, idx (B,S,k) int32, aux_loss scalar)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style aux: E * sum_e( frac_tokens_e * mean_prob_e )
    B, S, E = probs.shape
    onehot_top1 = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
    frac = jnp.mean(onehot_top1, axis=(0, 1))
    mean_p = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac * mean_p)
    return w, idx, aux


def _dispatch_compute_combine(x, idx, w, wi, wo, *, act: str, capacity: int,
                              num_experts: int, top_k: int,
                              ep_axis: Optional[str]):
    """Manual (per-shard) MoE body. x (B,S,D) local; idx/w (B,S,k) local."""
    B, S, D = x.shape
    T = B * S
    E, C, K = num_experts, capacity, top_k
    xf = x.reshape(T, D)
    flat_e = idx.reshape(T * K)                       # expert of each slot
    tok_of_slot = jnp.repeat(jnp.arange(T), K)

    # stable sort by expert -> position-within-expert via sorted cumcount
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    # rank within equal-expert runs: i - first_index_of(se[i])
    first_idx = jnp.searchsorted(se, jnp.arange(E), side="left")
    rank = jnp.arange(T * K) - first_idx[se]
    keep = rank < C
    dest = jnp.where(keep, se * C + rank, E * C)      # E*C = dropped sentinel

    # src[e*C + c] = flat token slot feeding that capacity cell (T*K = empty)
    src = jnp.full((E * C + 1,), T, jnp.int32)
    src = src.at[dest].set(tok_of_slot[order].astype(jnp.int32), mode="drop")
    src = src[:-1]
    xpad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
    xd = xpad[src].reshape(E, C, D)                   # dispatch buffer

    if ep_axis is not None:
        # (E, C, D) -> (E/tp, tp*C, D): tokens for my local experts
        xd = jax.lax.all_to_all(xd, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)
    wi = wi.astype(x.dtype)
    wo = wo.astype(x.dtype)

    h = jnp.einsum("ecd,edf->ecf", xd, wi)
    g, u = jnp.split(h, 2, axis=-1)
    g = jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)
    y = jnp.einsum("ecf,efd->ecd", g * u, wo)

    if ep_axis is not None:
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                               tiled=True)            # back to (E, C, D)

    # combine: slot s reads y[flat_e[s]*C + rank[s]] if kept
    ypad = jnp.concatenate([y.reshape(E * C, D),
                            jnp.zeros((1, D), y.dtype)], axis=0)
    slot_src = jnp.where(keep, se * C + rank, E * C)
    gathered = ypad[slot_src]                         # (T*K, D) in sorted order
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
    gathered = gathered[inv].reshape(T, K, D)
    wk = w.reshape(T, K, 1).astype(gathered.dtype)
    out = jnp.sum(gathered * wk, axis=1).reshape(B, S, D)
    return out


def apply_moe(p, cfg, x):
    """x (B,S,D) -> (B,S,D), aux_loss. Chooses manual EP when a mesh context
    maps 'act_expert' onto >1 devices; otherwise runs the same math locally.
    """
    m = cfg.moe
    w, idx, aux = _route(p["router"], x, m.num_experts, m.top_k)
    idx = idx.astype(jnp.int32)

    ctx = dist.current()
    ep_axes = ctx.mesh_axes("act_expert") if ctx else ()
    ep = len(ep_axes) == 1 and ctx.mesh.shape[ep_axes[0]] > 1 \
        and m.num_experts % ctx.mesh.shape[ep_axes[0]] == 0

    if not ep:
        T = x.shape[0] * x.shape[1]
        cap = max(int(math.ceil(T * m.top_k * m.capacity_factor
                                / m.num_experts)), m.top_k)
        routed = _dispatch_compute_combine(
            x, idx, w, p["wi"], p["wo"], act=cfg.act, capacity=cap,
            num_experts=m.num_experts, top_k=m.top_k, ep_axis=None)
    else:
        mesh = ctx.mesh
        ep_axis = ep_axes[0]
        tp = mesh.shape[ep_axis]
        dp = [a for a in ("pod", "data") if a in mesh.axis_names]
        dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
        B, S, D = x.shape
        bspec = tuple(dp) if (dp and B % dp_size == 0) else None
        seq_shard = tp if S % tp == 0 else 1
        sspec = ep_axis if seq_shard > 1 else None
        T_local = (B // (dp_size if bspec else 1)) * (S // seq_shard)
        cap = max(int(math.ceil(T_local * m.top_k * m.capacity_factor
                                / m.num_experts)), m.top_k)

        fn = jax.shard_map(
            functools.partial(
                _dispatch_compute_combine, act=cfg.act, capacity=cap,
                num_experts=m.num_experts, top_k=m.top_k, ep_axis=ep_axis),
            mesh=mesh,
            in_specs=(P(bspec, sspec, None), P(bspec, sspec, None),
                      P(bspec, sspec, None),
                      P(ep_axis, None, None), P(ep_axis, None, None)),
            out_specs=P(bspec, sspec, None),
            # when S doesn't shard over EP (decode: S=1), every EP shard
            # dispatches the same tokens and the inverse all_to_all returns
            # identical combines on every shard — replicated in value, but
            # the varying-manual-axes checker can't see through all_to_all
            check_vma=False,
        )
        routed = fn(x, idx, w.astype(x.dtype), p["wi"], p["wo"])

    if m.num_shared_experts:
        routed = routed + apply_mlp(p["shared"], x, cfg.act)
    return routed, aux
