"""Logical-axis sharding layer: the substrate every sharded code path sits on.

Model code never mentions mesh axes. Parameters and activations carry
*logical* axis names ("fsdp", "heads", "act_batch", ...) and this module
resolves them against the active mesh through a rule table:

    logical name  ->  tuple of mesh axes (resolved left to right)

Resolution for one tensor dimension (``DistContext.spec``):

  1. ``None`` (or a name with no rule / no mapped axis present in the
     mesh) -> the dim is replicated.
  2. Duplicate suppression: a dim whose mapped mesh axes intersect the
     axes already used by an earlier dim of the same spec replicates —
     a mesh axis can shard at most one dim of a tensor.
  3. Divisibility fallback: when the dim size is known and does not
     divide the mapped mesh-axis product, the dim replicates (e.g.
     whisper's 12 heads on a 16-wide "model" axis).

Context management is module-level so the same model code runs sharded
inside ``use_mesh(...)`` and unsharded outside it: ``constraint`` is the
single choke point — identity without a context, a
``jax.lax.with_sharding_constraint`` inside one.

The param-tree helpers implement the ZeRO-3 flavour used by the models:
master weights live "fsdp"-sharded (``param_sharding``) and the bf16
compute copy is all-gathered just-in-time (``gather_fsdp`` drops the
"fsdp" entry from each leaf's axes and re-constrains, which XLA turns
into an all-gather right before use).
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

Axes = Tuple[Optional[str], ...]
Rules = Dict[str, Tuple[str, ...]]

# Logical axis -> mesh axes. Mesh axes missing from the active mesh are
# skipped at resolution time, so one table covers the single-pod
# ("data", "model") and multi-pod ("pod", "data", "model") meshes.
DEFAULT_RULES: Rules = {
    # -- parameter axes -------------------------------------------------
    "fsdp": ("data",),          # ZeRO-3 weight/moment sharding
    "tp": ("model",),           # generic tensor-parallel dim
    "heads": ("model",),        # attention Q heads (TP)
    "kv_heads": ("model",),     # attention KV heads (TP)
    "ff": ("model",),           # MLP hidden dim (TP)
    "vocab": ("model",),        # embedding / logits vocab dim (TP)
    "expert": ("model",),       # MoE expert dim (EP)
    "layer": (),                # scan-stacked layer dim: never sharded
    # -- activation axes ------------------------------------------------
    "act_batch": ("pod", "data"),   # batch: pure DP across pods + data
    "act_seq": (),                  # sequence: replicated by default
    "act_seq_ckpt": ("model",),     # context-parallel fallback chunks
    "act_embed": (),                # d_model: replicated (norms are local)
    "act_vocab": ("model",),        # logits vocab dim
    "act_heads": ("model",),        # Q-head activations
    "act_kv_heads": ("model",),     # KV-head activations
    "act_kv_seq": ("model",),       # decode KV-cache sequence (flash-decode)
    "act_ff": ("model",),           # MLP hidden activations
    "act_expert": ("model",),       # MoE expert-parallel axis
    # -- fleet-monitoring axes -------------------------------------------
    "fleet_node": ("data",),        # detector node axis: peer-median rank
                                    # counts psum across node shards
}


class DistContext:
    """A mesh plus the rule table resolving logical axes onto it."""

    def __init__(self, mesh, rules: Optional[Rules] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES) if rules is None else dict(rules)

    # ------------------------------------------------------- resolution

    def mesh_axes(self, name: Optional[str]) -> Tuple[str, ...]:
        """Mesh axes a logical name maps to, restricted to this mesh.

        Unknown names (and ``None``) resolve to () — replicated — so
        model code can use new logical names before a rule exists.
        """
        if name is None:
            return ()
        present = self.mesh.axis_names
        return tuple(a for a in self.rules.get(name, ()) if a in present)

    def axis_size(self, name: Optional[str]) -> int:
        """Total shard count of a logical axis on this mesh (1 = unmapped)."""
        return math.prod(self.mesh.shape[a] for a in self.mesh_axes(name))

    def spec(self, logical_axes: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        """Resolve per-dim logical names to a PartitionSpec.

        ``shape`` (when given) enables the divisibility fallback; it must
        have the same rank as ``logical_axes``.
        """
        logical_axes = tuple(logical_axes)
        if shape is not None and len(shape) != len(logical_axes):
            raise ValueError(
                f"rank mismatch: axes {logical_axes} vs shape {tuple(shape)}")
        used: set = set()
        entries = []
        for i, name in enumerate(logical_axes):
            axes = self.mesh_axes(name)
            if not axes or used & set(axes):
                entries.append(None)
                continue
            size = math.prod(self.mesh.shape[a] for a in axes)
            if shape is not None and shape[i] % size != 0:
                entries.append(None)        # doesn't divide: replicate
                continue
            used.update(axes)
            entries.append(axes[0] if len(axes) == 1 else axes)
        return P(*entries)

    def sharding(self, logical_axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))


# ----------------------------------------------------------------- context

_context: Optional[DistContext] = None


def set_context(ctx: Optional[DistContext]) -> None:
    global _context
    _context = ctx


def current() -> Optional[DistContext]:
    return _context


@contextlib.contextmanager
def use_mesh(mesh, rules: Optional[Rules] = None):
    """Install a DistContext for the dynamic extent of the block.

    The prior context is restored on exit — including on exception — so
    nested meshes and failing tests can't leak sharding state.
    """
    prev = current()
    ctx = DistContext(mesh, rules)
    set_context(ctx)
    try:
        yield ctx
    finally:
        set_context(prev)


def constraint(x, *logical_axes: Optional[str]):
    """Apply a sharding constraint when a mesh context is active.

    Identity (returns ``x`` itself) without a context, so model code is
    unconditional; the divisibility fallback means a constraint can never
    make a layout invalid, only unconstrained.
    """
    ctx = current()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, ctx.sharding(logical_axes, x.shape))


# ------------------------------------------------------------ param trees


def _is_axes_leaf(x: Any) -> bool:
    """True for a logical-axes tuple like ("fsdp", None, "heads") or ().

    Distinguishes axes leaves from structural tuples (whose elements are
    themselves containers) in ``jax.tree.map`` traversals.
    """
    return isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)


def param_sharding(axes_tree, params_tree, ctx: Optional[DistContext] = None):
    """Axes tree + matching value tree -> tree of NamedShardings."""
    ctx = ctx if ctx is not None else current()
    if ctx is None:
        raise RuntimeError("param_sharding requires an active mesh context")
    return jax.tree.map(
        lambda ax, p: ctx.sharding(ax, p.shape),
        axes_tree, params_tree, is_leaf=_is_axes_leaf)


def gather_fsdp(tree, axes_tree):
    """ZeRO-3 just-in-time gather: re-constrain with "fsdp" dropped.

    Inside jit this compiles to an all-gather over the "data" axis right
    before the weights are consumed; other logical axes (TP/EP) keep
    their sharding. No-op without a context.
    """
    ctx = current()
    if ctx is None:
        return tree

    def gather(x, ax):
        gathered = tuple(None if a == "fsdp" else a for a in ax)
        return jax.lax.with_sharding_constraint(
            x, ctx.sharding(gathered, x.shape))

    return jax.tree.map(gather, tree, axes_tree)
