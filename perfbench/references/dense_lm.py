"""Plain reference of a dense decoder LM's training step (phi3-mini).

Written from the published description (arXiv:2404.14219; the
``microsoft/Phi-3-mini-4k-instruct`` config): pre-norm RMSNorm blocks,
rotary position embedding (half split), full multi-head causal
attention, SwiGLU MLP, untied LM head, mean token cross-entropy; AdamW
with global-norm clipping and linear warmup + cosine decay, as the
configuration's ``optimizer`` section states. Straight ``jax.numpy`` in
float32 at ``highest`` matmul precision, one layer at a time
(rematerialised) and a block of queries at a time, so that it fits on
one chip once the program's state is freed. It imports nothing of the
program.

Departures from the published model, as the configuration runs it: RMS
scales are stored as offsets from 1 (``x * (1 + s)``), and the
embedding and head keep 64 padded vocabulary rows that no token uses.

``init_params`` makes the weights from the seed; the benchmark hands
the same weights to the program, so the reference takes nothing the
program made. ``precision="fp8"`` computes every matmul as float8
training does (``_fp8_einsum``): the lower-precision control of a
configuration that states bfloat16 compute.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def param_shapes(cfg: dict) -> dict:
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    ff, n, vp = cfg["intermediate_size"], cfg["num_hidden_layers"], \
        padded_vocab(cfg)
    return {
        "embed": {"tok": (vp, d), "head": (d, vp)},
        "ln_f": (d,),
        "layers": {
            "ln_attn": (n, d),
            "attn": {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
                     "wv": (n, d, kv * hd), "wo": (n, h * hd, d)},
            "ln_mlp": (n, d),
            "mlp": {"wg": (n, d, ff), "wu": (n, d, ff), "wo": (n, ff, d)},
        },
    }


def key_for(seed: int):
    """A JAX key from a seed of any size."""
    seed = int(seed) & (2**64 - 1)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def init_params(cfg: dict, seed: int) -> dict:
    """float32 weights, made on the device in one jitted call: matrices
    N(0, 1/fan_in), token embedding N(0, 0.02^2), RMS offsets 0."""
    shapes = param_shapes(cfg)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple))
    return _maker(tuple(leaf_names(shapes)), tuple(flat), tree)(
        key_for(seed))


def _leaf(key, i: int, name: str, shp: tuple):
    if len(shp) == 1 or name.split("/")[-1].startswith("ln"):
        return jnp.zeros(shp, jnp.float32)
    scale = 0.02 if name == "embed/tok" else 1.0 / math.sqrt(shp[-2])
    return jax.random.normal(jax.random.fold_in(key, i), shp,
                             jnp.float32) * jnp.float32(scale)


@functools.lru_cache(maxsize=None)
def _maker(names, flat, tree):
    return jax.jit(lambda key: jax.tree.unflatten(tree, [
        _leaf(key, i, n, s) for i, (n, s) in enumerate(zip(names, flat))]))


@functools.lru_cache(maxsize=None)
def _leaf_change(i: int, name: str, shp: tuple):
    """Norm of (param - its initial value) for one leaf, per layer for
    stacked layer leaves. The initial value is made again here, one leaf
    at a time, so that no second copy of the weights is held; compiled
    alone it may differ from ``init_params``'s in the last bits, some
    1e-7 of the leaf's norm, far under any change being compared."""
    def f(key, p):
        d = p - _leaf(key, i, name, shp)
        axes = tuple(range(1, d.ndim)) if name.startswith("layers/") \
            else None
        return jnp.sqrt(jnp.sum(d * d, axis=axes))
    return jax.jit(f)


def leaf_names(tree) -> List[str]:
    paths = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0]
    return ["/".join(str(getattr(p, "key", p)) for p in path)
            for path, _ in paths]


# ------------------------------------------------------------ the model


def _round_fp8(x, dtype):
    """Round to a float8 type with a per-tensor scale (amax -> its max)."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.lru_cache(maxsize=None)
def _fp8_einsum(eq: str):
    """einsum as float8 training runs it: both operands rounded to e4m3
    going forward, the incoming gradient rounded to e5m2 going back, each
    with a per-tensor scale; products accumulate in float32."""
    hi = jax.lax.Precision.HIGHEST

    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(eq, _round_fp8(a, jnp.float8_e4m3fn),
                          _round_fp8(b, jnp.float8_e4m3fn), precision=hi)

    def fwd(a, b):
        a8 = _round_fp8(a, jnp.float8_e4m3fn)
        b8 = _round_fp8(b, jnp.float8_e4m3fn)
        return jnp.einsum(eq, a8, b8, precision=hi), (a8, b8)

    def bwd(res, g):
        a8, b8 = res
        _, vjp = jax.vjp(lambda a, b: jnp.einsum(eq, a, b, precision=hi),
                         a8, b8)
        return vjp(_round_fp8(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f


def _mm(precision: str):
    if precision == "fp8":
        return lambda a, b, eq: _fp8_einsum(eq)(a, b)
    return lambda a, b, eq: jnp.einsum(
        eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x, theta):
    _, s, _, hd = x.shape
    half = hd // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    c, sn = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * sn, a * sn + b * c], -1)


def _attention(mm, q, k, v, chunk):
    """Causal softmax attention, a block of queries at a time so that the
    scores held stay small. q, k, v: (B, S, H, hd)."""
    s, hd = q.shape[1], q.shape[3]
    chunk = min(chunk, s)
    outs = []
    for c0 in range(0, s, chunk):
        qc = q[:, c0:c0 + chunk]
        sc = mm(qc, k, "bqhd,bkhd->bhqk") / math.sqrt(hd)
        qpos = c0 + jnp.arange(qc.shape[1])
        causal = jnp.arange(s)[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        outs.append(mm(p, v, "bhqk,bkhd->bqhd"))
    return jnp.concatenate(outs, 1)


def _layer(cfg, precision, x, lp):
    mm = _mm(precision)
    b, s, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    a = _rms(x, lp["ln_attn"], eps)
    q = mm(a, lp["attn"]["wq"], "bsd,dk->bsk").reshape(b, s, h, hd)
    k = mm(a, lp["attn"]["wk"], "bsd,dk->bsk").reshape(b, s, kv, hd)
    v = mm(a, lp["attn"]["wv"], "bsd,dk->bsk").reshape(b, s, kv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    rep = h // kv
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    o = _attention(mm, q, k, v, cfg["reference_query_block"])
    x = x + mm(o.reshape(b, s, h * hd), lp["attn"]["wo"], "bsk,kd->bsd")
    m = _rms(x, lp["ln_mlp"], eps)
    g = mm(m, lp["mlp"]["wg"], "bsd,df->bsf")
    u = mm(m, lp["mlp"]["wu"], "bsd,df->bsf")
    return x + mm(jax.nn.silu(g) * u, lp["mlp"]["wo"], "bsf,fd->bsd")


def batch_loss(cfg, precision, params, tokens, labels):
    """Mean next-token cross-entropy over every token of the batch."""
    v = cfg["vocab_size"]
    x = params["embed"]["tok"][tokens]
    layer = jax.checkpoint(lambda x, lp: _layer(cfg, precision, x, lp))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
    x = _rms(x, params["ln_f"], cfg["rms_norm_eps"])
    logits = _mm(precision)(x, params["embed"]["head"][:, :v],
                            "bsd,dv->bsv")
    lse = jax.nn.logsumexp(logits, -1)
    own = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - own)


def lr_at(opt: dict, count: int) -> float:
    if count < opt["warmup_steps"]:
        return opt["peak_lr"] * count / max(opt["warmup_steps"], 1)
    prog = min(max((count - opt["warmup_steps"]) /
                   max(opt["total_steps"] - opt["warmup_steps"], 1), 0), 1)
    return opt["peak_lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"])
                             * 0.5 * (1 + math.cos(math.pi * prog)))


class Reference:
    """Steps of plain AdamW training from ``init_params(cfg, seed)``."""

    def __init__(self, cfg: dict, precision: str = "highest",
                 half_batch: bool = False):
        self.cfg = cfg
        self.precision = precision
        # fault used to read an upper limit: the loss and the gradient
        # are the mean over the first half of the batch only
        self.half_batch = half_batch
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, t, l: batch_loss(cfg, precision, p, t, l)))
        self._update = jax.jit(self._adamw, donate_argnums=(0, 1, 2, 3))

    def _adamw(self, params, mu, nu, grads, count, lr):
        opt = self.cfg["optimizer"]
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        b1, b2 = opt["b1"], opt["b2"]
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count

        def one(path, p, g, m, n):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            n = b2 * n + (1 - b2) * g * g
            u = (m / c1) / (jnp.sqrt(n / c2) + opt["eps"])
            # decoupled weight decay on weight matrices only
            per_layer_ndim = p.ndim - (path[0].key == "layers")
            if per_layer_ndim >= 2:
                u = u + opt["weight_decay"] * p
            return p - lr * u, m, n, g

        out = jax.tree_util.tree_map_with_path(one, params, grads, mu, nu)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), pick(3)

    def run(self, seed: int, batches: List[dict]) -> dict:
        """Losses of each step, per-leaf norms of the first clipped
        gradient, and per-leaf norms of the parameters' change after
        ``len(batches)`` steps. Holds the weights, Adam's two moments and
        one gradient: 4 x 4 bytes a parameter."""
        params = init_params(self.cfg, seed)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for i, batch in enumerate(batches, start=1):
            toks, labs = batch["tokens"], batch["labels"]
            if self.half_batch:
                toks, labs = toks[:len(toks) // 2], labs[:len(labs) // 2]
            loss, grads = self._grad(params, jnp.asarray(toks),
                                     jnp.asarray(labs))
            losses.append(float(loss))
            lr = lr_at(self.cfg["optimizer"], i)
            params, mu, nu, clipped = self._update(
                params, mu, nu, grads, jnp.float32(i), jnp.float32(lr))
            if i == 1:
                first = leaf_norms(clipped)
            del grads, clipped
        del mu, nu
        change = change_norms(params, self.cfg, seed)
        return {"losses": losses, "grad": first, "change": change}


def leaf_norms(tree) -> Dict[str, float]:
    """L2 norm of every leaf, stacked layer leaves split by layer."""
    return _named(tree, _norms_jit(tree))


def change_norms(params, cfg: dict, seed: int) -> Dict[str, float]:
    """``leaf_norms`` of ``params - init_params(cfg, seed)``."""
    key = key_for(seed)
    names = leaf_names(params)
    return _named(params, [
        _leaf_change(i, n, tuple(p.shape))(key, p)
        for i, (n, p) in enumerate(zip(names, jax.tree.leaves(params)))])


def _named(tree, norms) -> Dict[str, float]:
    out = {}
    for name, arr in zip(leaf_names(tree), norms):
        arr = np.asarray(arr)
        if arr.ndim == 0:
            out[name] = float(arr)
        else:
            for i, v in enumerate(arr):
                out[f"{name}[{i}]"] = float(v)
    return out


@jax.jit
def _norms_jit(tree):
    return _norms(tree)


def _norms(tree):
    def one(path, x):
        x = x.astype(jnp.float32)
        if path[0].key == "layers":
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))
    return jax.tree.leaves(jax.tree_util.tree_map_with_path(one, tree))
