"""Logical-axis sharding: names -> PartitionSpecs (MaxText-style).

Model code annotates every parameter dim and activation dim with a
*logical* axis name ("embed", "heads", "mlp", ...).  This module maps
those names onto the physical mesh axes:

  - ``DEFAULT_RULES`` encodes the production layout: tensor-parallel dims
    over 'model', FSDP parameter sharding over 'data', batch dims over
    ('pod', 'data').  Per-arch overrides (divisibility-driven) come from
    ``repro.models.registry.sharding_rules`` and are merged on top via
    ``use_mesh(mesh, rules)``.
  - ``logical_to_spec`` resolves one tuple of names to a ``PartitionSpec``
    with three safety rails: names not mapped (or mapped to mesh axes that
    don't exist) replicate; each mesh axis is used by at most one dim
    (first dim wins); a dim whose size is not divisible by its mesh-axes
    product replicates (when the shape is known).
  - ``constrain(x, *names)`` is the in-model annotation point: a no-op
    without an active ``use_mesh`` context, ``with_sharding_constraint``
    inside one — so the exact same model code runs single-device and on a
    512-chip mesh.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_BATCH = object()    # sentinel: resolve to batch_axes(mesh)

# production layout: TP over 'model', FSDP over 'data', batch over pods
DEFAULT_RULES: Dict[str, object] = {
    "batch": _BATCH,
    "attn_batch": None,
    "seq": None,
    "kv_seq": "model",
    "embed": "data",          # FSDP parameter sharding
    "vocab": "model",
    "heads": "model",
    "kv_heads": None,         # kv heads are few; replicate unless divisible
    "head_dim": None,
    "qkv": "model",
    "mlp": "model",
    "expert": None,
    "expert_mlp": "model",
    "inner": "model",
    "conv": None,
    "ssm_state": None,
    "dt_rank": None,
    "layers": None,
}


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    ``jax.make_mesh`` builds Explicit axes by default, and
    ``with_sharding_constraint`` (``constrain``) accepts only Auto axes:
    the model code here annotates shardings and lets the compiler
    propagate them."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def flat_mesh(num_devices: Optional[int] = None, axis_name: str = "data",
              devices=None) -> Mesh:
    """1-D mesh over the first ``num_devices`` available devices.

    The data-parallel shape used for embarrassingly parallel work
    (``repro.core.sweep`` shards scenario grids over it); ``num_devices``
    is clamped to what the platform actually has, so callers can ask for
    "all of them" (None) or a bound without counting devices first.
    Defaults to this process's ADDRESSABLE devices: under
    ``jax.distributed`` (repro.fleet multi-controller mode) the global
    ``jax.devices()`` includes other hosts' devices, which a
    single-process shard_map cannot address — identical outside it."""
    devs = list(devices) if devices is not None else jax.local_devices()
    n = len(devs) if num_devices is None else max(1, min(num_devices,
                                                         len(devs)))
    return make_mesh((n,), (axis_name,), devices=devs[:n])


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes the batch dim spans: ('pod', 'data') filtered to the mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _resolve(name: Optional[str], mesh, rules: Dict[str, object]):
    if name is None:
        return None
    entry = rules[name] if name in rules else DEFAULT_RULES.get(name)
    if entry is _BATCH:
        entry = batch_axes(mesh)
    return entry


def logical_to_spec(axes: Sequence[Optional[str]], mesh,
                    rules: Optional[Dict[str, object]] = None,
                    shape: Optional[Sequence[int]] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    ``mesh`` only needs ``.axis_names`` and ``.shape`` (a mapping), so
    mock meshes work for pure-logic tests.  Trailing ``None`` entries are
    trimmed so specs compare equal regardless of rank padding.
    """
    rules = rules or {}
    used: set = set()
    out = []
    for i, name in enumerate(axes):
        entry = _resolve(name, mesh, rules)
        if entry is None:
            out.append(None)
            continue
        as_tuple = isinstance(entry, tuple)
        names = tuple(entry) if as_tuple else (entry,)
        names = tuple(a for a in names
                      if a in mesh.axis_names and a not in used)
        if not names:
            out.append(None)
            continue
        size = 1
        for a in names:
            size *= mesh.shape[a]
        if shape is not None and shape[i] % size != 0:
            out.append(None)          # non-divisible dim: replicate
            continue
        used.update(names)
        out.append(names if as_tuple else names[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


# ---------------------------------------------------------------------------
# active-mesh context
# ---------------------------------------------------------------------------
_ACTIVE: list = []    # stack of (mesh, merged rules)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[Dict[str, object]] = None):
    """Activate (mesh, per-arch rule overrides) for ``constrain`` calls
    traced inside the context."""
    _ACTIVE.append((mesh, dict(rules or {})))
    try:
        yield mesh
    finally:
        _ACTIVE.pop()


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE[-1][0] if _ACTIVE else None


def active_rules() -> Dict[str, object]:
    return _ACTIVE[-1][1] if _ACTIVE else {}


def constrain(x, *axes: Optional[str]):
    """Annotate ``x``'s dims with logical names.  Identity without an
    active mesh; ``with_sharding_constraint`` inside ``use_mesh``."""
    if not _ACTIVE:
        return x
    mesh, rules = _ACTIVE[-1]
    spec = logical_to_spec(tuple(axes), mesh, rules, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and \
        all(e is None or isinstance(e, str) for e in x)


def shardings_for_axes(axes_tree, mesh: Mesh, shape_tree=None,
                       rules: Optional[Dict[str, object]] = None):
    """Pytree of logical-axes tuples -> pytree of NamedShardings.

    Uses the active ``use_mesh`` rules when none are passed.  With
    ``shape_tree`` (matching tree of arrays / ShapeDtypeStructs),
    non-divisible dims auto-replicate."""
    if rules is None:
        rules = active_rules()

    def one(ax, sds=None):
        shape = None if sds is None else sds.shape
        return NamedSharding(mesh, logical_to_spec(ax, mesh, rules,
                                                   shape=shape))

    if shape_tree is None:
        return jax.tree.map(one, axes_tree, is_leaf=_is_axes_leaf)
    return jax.tree.map(one, axes_tree, shape_tree, is_leaf=_is_axes_leaf)
