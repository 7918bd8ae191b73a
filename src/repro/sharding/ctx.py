"""Logical-axis sharding context.

Model code annotates activations with *logical* axis names via ``shard(x,
axes)``; the launcher binds logical names to mesh axes with ``use_rules``.
Outside any binding (unit tests, single device) ``shard`` is the identity —
the models stay mesh-agnostic, mirroring the paper's split between
application code and platform-owned placement.

Rule sets are plain dicts: logical name -> mesh axis (str), tuple of mesh
axes, or None.  Unknown names shard to None (replicated).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _current() -> Optional[tuple]:
    return getattr(_state, "binding", None)


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.

    The model places activations with sharding constraints and leaves the
    rest to the compiler's propagation, which JAX allows on ``Auto`` axes
    only; ``jax.make_mesh`` makes ``Explicit`` axes by default.
    """
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@contextmanager
def use_rules(mesh: Mesh, rules: dict):
    """Bind logical names to ``mesh``.  Rule targets the mesh lacks (e.g.
    ``pod`` on a 2-D ``(data, model)`` mesh) are dropped, so one rule set
    serves every mesh shape."""
    prev = _current()
    missing = {a for v in rules.values() if v is not None
               for a in (v if isinstance(v, tuple) else (v,))
               if a not in mesh.axis_names}
    _state.binding = (auto_axes(mesh), _strip_axes(rules, missing))
    try:
        yield
    finally:
        _state.binding = prev


def resolve(axes: tuple, rules: dict) -> P:
    parts = []
    for a in axes:
        if a is None:
            parts.append(None)
        else:
            parts.append(rules.get(a))
    return P(*parts)


def shard(x: jax.Array, axes: tuple) -> jax.Array:
    """Constrain ``x`` to the sharding implied by logical ``axes``.

    Inside a partial-manual shard_map region (e.g. the compressed-gradient
    path, manual over 'pod'), constraints must be built against the current
    *abstract* mesh — its axis types carry the Manual marking — and must not
    mention manual axes.
    """
    binding = _current()
    if binding is None:
        return x
    mesh, rules = binding
    abstract = jax.sharding.get_abstract_mesh()
    if not abstract.empty:
        manual = {name for name, kind in zip(abstract.axis_names,
                                             abstract.axis_types)
                  if str(kind).endswith("Manual")}
        if manual:
            rules = _strip_axes(rules, manual)
            spec = resolve(axes, rules)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(abstract, spec))
    spec = resolve(axes, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _strip_axes(rules: dict, banned: set) -> dict:
    out = {}
    for k, v in rules.items():
        if isinstance(v, tuple):
            v = tuple(a for a in v if a not in banned) or None
        elif v in banned:
            v = None
        out[k] = v
    return out


# ---------------------------------------------------------------- rule sets


def activation_rules(
    *,
    data_axes: tuple = ("pod", "data"),
    model_axis: str = "model",
    sequence_parallel: bool = False,
    shard_cache_seq: bool = False,
) -> dict:
    """Standard rule set for the (pod, data, model) production mesh.

    - ``batch``/``dp`` over the pure-DP axes,
    - heads / ff / vocab / experts over the tensor axis,
    - ``seq``: sharded over the tensor axis between blocks iff
      ``sequence_parallel`` (the SP hillclimb lever),
    - ``cache_seq``: KV-cache sequence axis; sharding it over the tensor
      axis is the flash-decode/split-K lever for MQA decode.
    """
    return {
        "batch": data_axes,
        "dp": data_axes,
        "seq": model_axis if sequence_parallel else None,
        "heads": model_axis,
        "kv_heads": model_axis,
        "ff": model_axis,
        "vocab": model_axis,
        "expert": model_axis,
        "rnn": model_axis,
        "cache_seq": model_axis if shard_cache_seq else None,
        "fsdp": "data",
        "embed": None,
    }
