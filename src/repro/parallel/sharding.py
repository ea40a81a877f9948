"""Logical-axis sharding: names -> mesh axes -> NamedSharding.

Every parameter / activation dimension carries a *logical* axis name
("embed", "heads", "batch", ...). An :class:`AxisRules` table maps each
logical name to zero or more mesh axes. The same model code therefore runs
on the single-pod ``(data, model)`` mesh and the multi-pod
``(pod, data, model)`` mesh: rules that reference a mesh axis absent from
the current mesh are silently dropped (e.g. "pod" on a single-pod mesh).

This is the hillclimbing control surface: a perf iteration swaps the rules
table, not the model code.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field, replace

import jax
from jax import shard_map as _shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axis_shapes, axis_names, *, devices=None, axis_types=None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto unless ``axis_types`` says
    otherwise (the behaviour this repo assumes)."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=axis_types)


def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` without replication checks; ``axis_names`` makes it
    manual over that subset of the mesh axes only."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False, **kw)


# ---------------------------------------------------------------------------
# Rules


@dataclass(frozen=True)
class AxisRules:
    """Mapping of logical axis names to (tuples of) mesh axis names."""

    rules: dict[str, tuple[str, ...]] = field(default_factory=dict)

    def get(self, logical: str | None) -> tuple[str, ...]:
        if logical is None:
            return ()
        return self.rules.get(logical, ())

    def override(self, **kw: tuple[str, ...] | str | None) -> "AxisRules":
        new = dict(self.rules)
        for k, v in kw.items():
            if v is None:
                new[k] = ()
            elif isinstance(v, str):
                new[k] = (v,)
            else:
                new[k] = tuple(v)
        return replace(self, rules=new)


# The baseline production ruleset: DP over (pod, data), FSDP weight sharding
# over data, TP over model, EP (experts) over model, decode-cache SP over
# model.  See DESIGN.md §5.
DEFAULT_RULES = AxisRules(
    {
        # activations
        "batch": ("pod", "data"),
        "seq": (),                 # sequence replicated in train fwd
        "act_embed": (),           # d_model dim of activations
        "act_heads": ("model",),   # per-head activation dims
        "act_ffn": ("model",),
        "act_vocab": ("model",),
        # weights (FSDP dim = "embed"; TP dims = heads/ffn/vocab)
        "embed": ("data",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "qkv_flat": ("model",),
        "ffn": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "expert_ffn": (),
        "layers": (),
        "stack": (),
        # recurrent / ssm state
        "ssm_heads": ("model",),
        "ssm_state": (),
        "conv_dim": ("model",),
        # serving caches
        "cache_batch": ("pod", "data"),
        "cache_seq": ("model",),   # SP over the KV cache during decode
        "cache_kv_heads": (),
        # misc
        "norm": (),
    }
)


# ---------------------------------------------------------------------------
# Context


class _Ctx(threading.local):
    mesh: Mesh | None = None
    rules: AxisRules | None = None


_CTX = _Ctx()


class sharding_context:
    """Install ``mesh`` + ``rules`` for :func:`logical_sharding` / :func:`shard_act`.

    Reentrant/reusable (unlike a generator-based contextmanager)."""

    def __init__(self, mesh: Mesh, rules: AxisRules = DEFAULT_RULES):
        self.mesh, self.rules = mesh, rules
        self._prev: list[tuple] = []

    def __enter__(self):
        self._prev.append((_CTX.mesh, _CTX.rules))
        _CTX.mesh, _CTX.rules = self.mesh, self.rules
        return self

    def __exit__(self, *exc):
        _CTX.mesh, _CTX.rules = self._prev.pop()
        return False


def current_mesh() -> Mesh | None:
    return _CTX.mesh


def current_rules() -> AxisRules:
    return _CTX.rules if _CTX.rules is not None else DEFAULT_RULES


# ---------------------------------------------------------------------------
# Spec construction


def _spec_for(logical_axes: tuple[str | None, ...], mesh: Mesh, rules: AxisRules,
              shape: tuple[int, ...] | None = None) -> P:
    """PartitionSpec for one array: drops mesh axes not in the mesh, never
    reuses a mesh axis, and — when ``shape`` is given — drops axes that do
    not divide the dimension evenly (jit argument/output shardings must
    tile exactly; intermediates via shard_act may still pad)."""
    used: set[str] = set()
    parts = []
    for i, name in enumerate(logical_axes):
        axes = []
        prod = 1
        for a in rules.get(name):
            if a not in mesh.axis_names or a in used:
                continue
            n = mesh.shape[a]
            if shape is not None and shape[i] % (prod * n) != 0:
                continue
            axes.append(a)
            prod *= n
        used.update(axes)
        if len(axes) == 0:
            parts.append(None)
        elif len(axes) == 1:
            parts.append(axes[0])
        else:
            parts.append(tuple(axes))
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def logical_sharding(
    logical_axes: tuple[str | None, ...],
    mesh: Mesh | None = None,
    rules: AxisRules | None = None,
    shape: tuple[int, ...] | None = None,
) -> NamedSharding:
    mesh = mesh or _CTX.mesh
    if mesh is None:
        raise RuntimeError("logical_sharding: no mesh (use sharding_context)")
    rules = rules or current_rules()
    return NamedSharding(mesh, _spec_for(tuple(logical_axes), mesh, rules, shape))


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def tree_shardings(axes_tree, shapes_tree=None, mesh: Mesh | None = None,
                   rules: AxisRules | None = None):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.

    ``shapes_tree`` (matching pytree of ShapeDtypeStructs/arrays) enables
    divisibility-aware axis dropping.
    """
    if shapes_tree is None:
        return jax.tree.map(lambda ax: logical_sharding(ax, mesh, rules),
                            axes_tree, is_leaf=_is_axes_leaf)
    return jax.tree.map(
        lambda ax, sd: logical_sharding(ax, mesh, rules, tuple(sd.shape)),
        axes_tree, shapes_tree, is_leaf=_is_axes_leaf)


def shard_act(x, *logical_axes: str | None):
    """Activation sharding constraint (no-op outside a sharding_context)."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    rules = current_rules()
    spec = _spec_for(tuple(logical_axes), mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
