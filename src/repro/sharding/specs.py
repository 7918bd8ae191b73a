"""Parameter partition specs, derived — not stored.

Logical axes for every parameter are *computed* from the parameter tree's
path structure (the paper's hierarchical deterministic naming applied to
shardings: given (arch, mesh, rules), every placement is recomputable;
nothing about layout is ever persisted).

Param logical-axis vocabulary:
  embed_p — model width dim of params      -> FSDP axis ("data")
  vocab   — vocabulary dim                 -> tensor axis ("model")
  heads   — attention heads                -> tensor axis
  ff      — MLP hidden / mLSTM inner dim   -> tensor axis
  expert  — MoE expert dim                 -> tensor axis (EP)
  rnn     — RG-LRU recurrence width        -> tensor axis
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from .ctx import auto_axes


PARAM_RULES = {
    "embed_p": "data",
    "vocab": "model",
    "heads": "model",
    "ff": "model",
    "expert": "model",
    "rnn": "model",
}


def _leaf_axes(names: list, rank: int) -> tuple:
    """Logical axes for a parameter leaf, by name + context + rank."""
    name = names[-1]
    ctx = set(names)

    def r(*axes):
        assert len(axes) == rank, (names, rank, axes)
        return tuple(axes)

    if name == "table":
        return r("vocab", "embed_p")
    if name == "w" and "frontend" in ctx:
        return r(None, "embed_p")
    if name == "w" and "head" in ctx:
        return r("embed_p", "vocab")
    if name in ("scale",):
        return r(None)
    if "slstm" in ctx:
        if name in ("w_z", "w_i", "w_f", "w_o"):
            return r("embed_p", None)
        if name.startswith("r_"):
            return r("heads", None, None)
        if name == "w_o_proj":
            return r("embed_p", None)
        if name.startswith("b_"):
            return r(None)
        # fall through for the inner ffn (w_gate/w_up/w_down)
    if "rglru" in ctx:
        if name in ("w_x", "w_g"):
            return r("embed_p", "rnn")
        if name == "conv_w":
            return r(None, "rnn")
        if name in ("conv_b", "b_a", "b_i", "lam"):
            return r("rnn")
        if name in ("w_a", "w_i"):
            return r(None, "rnn")
        if name == "w_o":
            return r("rnn", "embed_p")
    if "mlstm" in ctx:
        if name == "w_up":
            return r("embed_p", "ff")
        if name == "conv_w":
            return r(None, "ff")
        if name == "conv_b":
            return r("ff")
        if name in ("wq", "wk", "wv"):
            return r("ff", "heads", None)
        if name in ("w_i", "w_f"):
            return r("ff", None)
        if name in ("b_i", "b_f"):
            return r(None)
        if name == "w_down":
            return r("ff", "embed_p")
    if name in ("wq", "wk", "wv"):
        return r("embed_p", "heads", None)
    if name == "wo":
        return r("heads", None, "embed_p")
    if name in ("bq", "bk", "bv"):
        return r("heads", None)
    if name == "router":
        return r("embed_p", "expert")
    if name == "shared_gate":
        return r("embed_p", None)
    if name in ("w_gate", "w_up"):
        return r("expert", "embed_p", None) if rank == 3 else r("embed_p", "ff")
    if name == "w_down":
        return r("expert", None, "embed_p") if rank == 3 else r("ff", "embed_p")
    if name == "conv_w":
        return r(None, "ff")
    if name in ("conv_b", "lam"):
        return r("ff")
    # biases / scalars: replicated
    return tuple(None for _ in range(rank))


def param_logical_axes(params) -> object:
    """Pytree (matching params) of logical-axis tuples."""

    def f(path, leaf):
        names = [p.key for p in path if isinstance(p, DictKey)]
        stacked = any(isinstance(p, SequenceKey) for p in path) and "main" in names
        # "main" segment params carry a leading scanned-layer dim
        is_main = names and names[0] == "main"
        rank = leaf.ndim - (1 if is_main else 0)
        axes = _leaf_axes(names, rank)
        return ((None,) + axes) if is_main else axes

    return jax.tree_util.tree_map_with_path(f, params)


def logical_to_spec(axes: tuple, rules: dict) -> P:
    return P(*[rules.get(a) if a is not None else None for a in axes])


def fit_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop sharding on dims the mesh axes don't divide (e.g. MQA kv=1 over a
    16-way tensor axis -> replicate that dim).  The resulting redundancy is
    visible in the roofline's MODEL_FLOPS/HLO ratio rather than hidden."""
    parts = []
    for i, p in enumerate(tuple(spec)[: len(shape)]):
        if p is None:
            parts.append(None)
            continue
        axes = p if isinstance(p, tuple) else (p,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        parts.append(p if shape[i] % size == 0 else None)
    return P(*parts)


def param_specs(params, mesh: Mesh, rules: dict = PARAM_RULES):
    """Pytree of NamedShardings for a (possibly abstract) parameter tree."""
    mesh = auto_axes(mesh)
    logical = param_logical_axes(params)
    return jax.tree.map(
        lambda leaf, ax: NamedSharding(
            mesh, fit_spec(logical_to_spec(ax, rules), leaf.shape, mesh)),
        params, logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )
