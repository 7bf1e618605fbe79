"""Attention: GQA projections, chunked causal/local attention (bounded
activation memory, scan-based), cross-attention, and decode-step attention
with KV-cache *sequence sharding* (flash-decoding style partial attention
combined via psum/pmax inside shard_map).
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
from repro.models.layers import apply_mrope, apply_rope, rms_norm


# ---------------------------------------------------------------- params


def init_attention(keys, cfg, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    p = {
        "wq": cm.dense(next(keys), d, nq * hd, ("fsdp", "heads")),
        "wk": cm.dense(next(keys), d, nkv * hd, ("fsdp", "kv_heads")),
        "wv": cm.dense(next(keys), d, nkv * hd, ("fsdp", "kv_heads")),
        "wo": cm.dense(next(keys), nq * hd, d, ("heads", "fsdp")),
    }
    if cfg.qkv_bias:
        p["bq"] = cm.zeros((nq * hd,), ("heads",))
        p["bk"] = cm.zeros((nkv * hd,), ("kv_heads",))
        p["bv"] = cm.zeros((nkv * hd,), ("kv_heads",))
    if cfg.qk_norm:
        p["q_norm"] = cm.zeros((hd,), (None,))
        p["k_norm"] = cm.zeros((hd,), (None,))
    return p


def project_qkv(p, cfg, x, positions=None, *, rope: bool = True):
    """x (B,S,D) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd) with RoPE applied."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    # constrain BEFORE RoPE: the rotate-half split/concat on an
    # unconstrained layout makes GSPMD shard head_dim and reshard through
    # all-to-alls; pinning heads-sharded/hd-replicated here keeps the
    # rotation entirely local
    q = dist.constraint(q, "act_batch", None, "act_heads", None)
    k = dist.constraint(k, "act_batch", None, "act_kv_heads", None)
    v = dist.constraint(v, "act_batch", None, "act_kv_heads", None)
    if rope:
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
        if cfg.mrope_sections:
            q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        q = dist.constraint(q, "act_batch", None, "act_heads", None)
        k = dist.constraint(k, "act_batch", None, "act_kv_heads", None)
    return q, k, v


def out_projection(p, attn_out):
    """attn_out (B,S,Hq,hd) -> (B,S,D)."""
    B, S, H, hd = attn_out.shape
    return jnp.einsum("bsh,hd->bsd", attn_out.reshape(B, S, H * hd), p["wo"])


# ---------------------------------------------------------------- core math


def _grouped_scores(qc, k):
    """qc (B,C,Hkv,G,hd) x k (B,T,Hkv,hd) -> (B,Hkv,G,C,T) fp32 logits."""
    return jnp.einsum("bchgd,bthd->bhgct", qc, k,
                      preferred_element_type=jnp.float32)


def _grouped_out(probs, v):
    """probs (B,Hkv,G,C,T) x v (B,T,Hkv,hd) -> (B,C,Hkv,G,hd)."""
    return jnp.einsum("bhgct,bthd->bchgd", probs.astype(v.dtype), v)


def _softmax_masked(scores, mask):
    scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _seq_shard_fallback(Hq: int, chunk: int) -> bool:
    """True when attention heads can NOT be sharded over the TP axis (e.g.
    llama4's 40 or whisper's 12 heads on a 16-wide axis) but the query
    chunk can — context-parallel attention instead of replicated attention
    (16x the FLOPs/memory otherwise)."""
    ctx = dist.current()
    if ctx is None:
        return False
    size = ctx.axis_size("act_heads")
    return size > 1 and Hq % size != 0 and chunk % size == 0


def full_attention(q, k, v, *, causal: bool, chunk: int = 2048,
                   q_offset: int = 0):
    """Query-chunked attention with bounded score memory.

    q (B,S,Hq,hd), k/v (B,T,Hkv,hd). ``lax.scan`` over query chunks keeps the
    HLO compact and the live score tensor at (B,Hkv,G,chunk,T).
    """
    B, S, Hq, hd = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S  # irregular small seq: single chunk
    nc = S // chunk
    seq_fallback = _seq_shard_fallback(Hq, chunk)
    qg = q.reshape(B, nc, chunk, Hkv, G, hd)
    qg = jnp.moveaxis(qg, 1, 0)                     # (nc,B,C,Hkv,G,hd)
    kpos = jnp.arange(T)

    def body(_, args):
        ci, qc = args
        if seq_fallback:
            qc = dist.constraint(qc, "act_batch", "act_seq_ckpt",
                                 None, None, None)
        scores = _grouped_scores(qc, k) * scale     # (B,Hkv,G,C,T)
        qpos = q_offset + ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, T), bool) if not causal else (
            kpos[None, :] <= qpos[:, None])
        probs = _softmax_masked(scores, mask[None, None, None])
        o = _grouped_out(probs, v)
        if seq_fallback:
            o = dist.constraint(o, "act_batch", "act_seq_ckpt",
                                None, None, None)
        return None, o

    _, out = jax.lax.scan(body, None, (jnp.arange(nc), qg))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, Hq, hd)
    return dist.constraint(out, "act_batch", None, "act_heads", None)


def local_attention(q, k, v, *, window: int):
    """Sliding-window causal attention, O(S·W): each window-sized query chunk
    attends to itself + the previous chunk (covers all offsets < window)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    W = window
    scale = 1.0 / math.sqrt(hd)
    if S <= W:
        return full_attention(q, k, v, causal=True, chunk=min(2048, S))
    assert S % W == 0, (S, W)
    nc = S // W
    seq_fallback = _seq_shard_fallback(Hq, W)
    qg = jnp.moveaxis(q.reshape(B, nc, W, Hkv, G, hd), 1, 0)
    # left-pad keys with one window so chunk i slices [(i-1)W, (i+1)W)
    kp = jnp.pad(k, ((0, 0), (W, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (W, 0), (0, 0), (0, 0)))

    def body(_, args):
        ci, qc = args
        if seq_fallback:
            qc = dist.constraint(qc, "act_batch", "act_seq_ckpt",
                                 None, None, None)
        ks = jax.lax.dynamic_slice_in_dim(kp, ci * W, 2 * W, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, ci * W, 2 * W, axis=1)
        scores = _grouped_scores(qc, ks) * scale    # (B,Hkv,G,W,2W)
        qpos = ci * W + jnp.arange(W)
        kpos = (ci - 1) * W + jnp.arange(2 * W)
        mask = ((kpos[None, :] <= qpos[:, None]) &
                (kpos[None, :] > qpos[:, None] - W) &
                (kpos[None, :] >= 0))
        probs = _softmax_masked(scores, mask[None, None, None])
        o = _grouped_out(probs, vs)
        if seq_fallback:
            o = dist.constraint(o, "act_batch", "act_seq_ckpt",
                                None, None, None)
        return None, o

    _, out = jax.lax.scan(body, None, (jnp.arange(nc), qg))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, Hq, hd)
    return dist.constraint(out, "act_batch", None, "act_heads", None)


def cross_attention(q, k, v):
    return full_attention(q, k, v, causal=False, chunk=2048)


# ---------------------------------------------------------------- decode


def _decode_inner(q, kc, vc, k_new, v_new, pos, *, axis: Optional[str],
                  window_offset=0):
    """Partial attention over a (possibly sequence-sharded) KV cache.

    q (B,Hq,hd); kc/vc (B,Hkv,Sl,hd) local shard; k_new/v_new (B,Hkv,hd);
    pos scalar int32 (global position to write + last visible position).
    Combines across `axis` shards with pmax/psum (flash-decoding).
    """
    B, Hq, hd = q.shape
    Hkv, Sl = kc.shape[1], kc.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    shard = jax.lax.axis_index(axis) if axis else 0
    offset = shard * Sl + window_offset

    # --- predicated cache insert (small read-modify-write, no full copy)
    loc = jnp.clip(pos - offset, 0, Sl - 1)
    ok = ((pos - offset) >= 0) & ((pos - offset) < Sl)

    def insert(cache, new):
        cur = jax.lax.dynamic_slice(cache, (0, 0, loc, 0), (B, Hkv, 1, hd))
        val = jnp.where(ok, new[:, :, None, :], cur)
        return jax.lax.dynamic_update_slice(cache, val, (0, 0, loc, 0))

    kc = insert(kc, k_new)
    vc = insert(vc, v_new)

    qg = q.reshape(B, Hkv, G, hd)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg, kc,
                        preferred_element_type=jnp.float32) * scale
    kpos = offset + jnp.arange(Sl)
    mask = (kpos <= pos)[None, None, None, :]
    scores = jnp.where(mask, scores, -1e30)

    m_loc = jnp.max(scores, axis=-1)                       # (B,Hkv,G)
    if axis:
        m = jax.lax.pmax(m_loc, axis)
    else:
        m = m_loc
    e = jnp.exp(scores - m[..., None])
    l_loc = jnp.sum(e, axis=-1)
    o_loc = jnp.einsum("bhgs,bhsd->bhgd", e.astype(vc.dtype), vc)
    if axis:
        l = jax.lax.psum(l_loc, axis)
        o = jax.lax.psum(o_loc, axis)
    else:
        l, o = l_loc, o_loc
    out = (o / jnp.maximum(l[..., None], 1e-30).astype(o.dtype)).reshape(B, Hq, hd)
    return out.astype(q.dtype), kc, vc


def decode_attention(q, kcache, vcache, k_new, v_new, pos, *,
                     window_offset=0):
    """Decode-step attention w/ cache insert. Uses shard_map sequence-parallel
    partial attention when a mesh context shards 'act_kv_seq'; otherwise runs
    locally. Returns (out (B,Hq,hd), kcache, vcache).
    """
    ctx = dist.current()
    seq_axes = ctx.mesh_axes("act_kv_seq") if ctx else ()
    Sl = kcache.shape[2]
    use_shard = bool(seq_axes) and dist.current().axis_size("act_kv_seq") > 1 \
        and Sl % dist.current().axis_size("act_kv_seq") == 0
    if not use_shard:
        return _decode_inner(q, kcache, vcache, k_new, v_new, pos, axis=None,
                             window_offset=window_offset)

    assert len(seq_axes) == 1, seq_axes
    axis = seq_axes[0]
    mesh = ctx.mesh
    B = q.shape[0]
    dp = [a for a in ("pod", "data") if a in mesh.axis_names]
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    bspec = tuple(dp) if (dp and B % dp_size == 0) else None

    fn = jax.shard_map(
        functools.partial(_decode_inner, axis=axis,
                          window_offset=window_offset),
        mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, axis, None),
                  P(bspec, None, axis, None), P(bspec, None, None),
                  P(bspec, None, None), P()),
        out_specs=(P(bspec, None, None), P(bspec, None, axis, None),
                   P(bspec, None, axis, None)),
    )
    return fn(q, kcache, vcache, k_new, v_new, pos)
