"""Parameter/state sharding rules: pytree path → logical axes → PartitionSpec.

Implements the paper's §3 partitioning on the TPU mesh:
  * Megatron-TP of attention & dense MLP  → ``model`` axis
  * MLA: W^UQ/W^UK/W^UV/W^O split, W^DQ/W^DKV/W^QR/W^KR replicated (§3.2);
    without a query LoRA, W^Q split by heads
  * EP: routed experts sharded on the expert dim; shared expert replicated
    (§3.3); ETP=1 → expert matrices unsplit internally
  * ZeRO (§4): optimizer state (os), gradients (os+g), parameters
    (os+g+params) additionally sharded across the data(+pod) axes — the
    GSPMD equivalent of DeepSpeed's DP-group partitioning.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.parallel_config import ZeROStage
from .axes import DEFAULT_RULES, param_partition_spec

PyTree = Any

# leaf-name → logical axes (stacked-layer leading dim handled separately)
_ATTN_RULES = {
    "wq": ("embed", "qkv"), "wk": ("embed", "qkv"), "wv": ("embed", "qkv"),
    "wo": ("qkv", "embed"),
    "bq": ("qkv",), "bk": ("qkv",), "bv": ("qkv",),
    "w_dq": ("embed", None), "w_uq": (None, "qkv"), "w_qr": (None, "qkv"),
    "w_dkv": ("embed", None), "w_uk": (None, "qkv"), "w_uv": (None, "qkv"),
    "w_kr": ("embed", None), "w_o": ("qkv", "embed"),
    "w_q": ("embed", "qkv"),
}
_SSM_RULES = {
    "w_r": ("embed", "ff"), "w_k": ("embed", "ff"), "w_v": ("embed", "ff"),
    "w_g": ("embed", "ff"), "w_o": ("ff", "embed"),
    "decay_a": ("embed", None), "decay_b": (None, "ff"),
    "u": ("ff",), "mu": (None, None), "conv": (None, "ff"),
}
_MLP_RULES = {
    "gate": ("embed", "ff"), "up": ("embed", "ff"), "down": ("ff", "embed"),
    "fc1": ("embed", "ff"), "fc2": ("ff", "embed"),
}
_MOE_RULES = {
    "router": ("embed", None),
    "we_gate": ("expert", None, "expert_ff"),
    "we_up": ("expert", None, "expert_ff"),
    "we_down": ("expert", "expert_ff", None),
}


def _leaf_axes(path: Tuple[str, ...], ndim: int) -> Tuple[Optional[str], ...]:
    keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = keys[-1]
    parents = set(keys[:-1])

    if "embed" in parents:
        base: Tuple[Optional[str], ...] = ("vocab", "embed")
    elif "head" in parents:
        base = ("embed", "vocab")
    elif name == "scale":                      # any norm
        base = ("embed",)
    elif "moe" in parents and name in _MOE_RULES:
        base = _MOE_RULES[name]
    elif ("shared" in parents or "mlp" in parents) and name in _MLP_RULES:
        base = _MLP_RULES[name]
    elif "ssm" in parents and name in _SSM_RULES:
        base = _SSM_RULES[name]
    elif name in _ATTN_RULES:                  # attn / xattn
        base = _ATTN_RULES[name]
    elif name in _MLP_RULES:
        base = _MLP_RULES[name]
    else:
        base = (None,) * ndim
    # stacked layer groups carry a leading layer dim
    if ndim == len(base) + 1:
        return (None,) + tuple(base)
    if ndim == len(base):
        return tuple(base)
    # e.g. vmapped extra dims: pad with None in front
    return (None,) * (ndim - len(base)) + tuple(base)


def _drop_indivisible(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Replicate any dim whose size isn't divisible by its mesh-axes product
    (e.g. hymba's vocab=32001)."""
    entries = []
    for dim, e in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if e is None:
            entries.append(None)
            continue
        ns = (e,) if isinstance(e, str) else tuple(e)
        size = int(np.prod([mesh.shape[n] for n in ns]))
        entries.append(e if dim % size == 0 else None)
    return P(*entries)


def param_specs(params: PyTree, mesh: Mesh, rules=None) -> PyTree:
    """PartitionSpec pytree mirroring ``params`` (abstract or concrete)."""

    def spec_for(path, leaf):
        shape = leaf.shape
        axes = _leaf_axes(path, getattr(leaf, "ndim", len(shape)))
        return _drop_indivisible(
            param_partition_spec(axes, mesh, rules), shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _dims_ok(shape: Sequence[int], spec: P, mesh: Mesh) -> bool:
    for dim, names in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if names is None:
            continue
        ns = (names,) if isinstance(names, str) else names
        size = int(np.prod([mesh.shape[n] for n in ns]))
        if dim % size:
            return False
    return True


def add_dp_axes(spec: P, shape: Sequence[int], mesh: Mesh,
                dp_axes: Sequence[str] = ("pod", "data")) -> P:
    """ZeRO: extend ``spec`` with the data(+pod) axes on the first dimension
    where the result stays legal (divisible, axes unused).  Falls back to the
    original spec when nothing fits (tiny tensors stay replicated — same as
    DeepSpeed's small-tensor handling)."""
    dp_axes = tuple(a for a in dp_axes if a in mesh.axis_names)
    if not dp_axes:
        return spec
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    used = set()
    for e in entries:
        if e is None:
            continue
        used.update((e,) if isinstance(e, str) else e)
    if any(a in used for a in dp_axes):
        return spec
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    for i, (dim, e) in enumerate(zip(shape, entries)):
        existing = () if e is None else ((e,) if isinstance(e, str) else tuple(e))
        ex_size = int(np.prod([mesh.shape[n] for n in existing])) if existing else 1
        if dim % (ex_size * dp_size) == 0:
            entries[i] = tuple(existing) + dp_axes
            return P(*entries)
    return spec


def pipeline_stage_specs(stacked: PyTree, mesh: Mesh, rules=None) -> PyTree:
    """PartitionSpec tree for stage-stacked pipeline params
    (``models.pipeline.stack_pipeline_params``): the leading stage dim maps to
    the ``pipe`` mesh axis; remaining dims follow the usual §3 leaf rules
    (so per-stage TP still applies on meshes that carry a model axis).  ZeRO
    DP-sharding within a stage is unchanged — apply ``add_dp_axes`` on top
    exactly as for pp=1 state."""

    def spec_for(path, leaf):
        axes = _leaf_axes(path, leaf.ndim)
        base = param_partition_spec(axes, mesh, rules)
        entries = list(tuple(base) + (None,) * (leaf.ndim - len(tuple(base))))
        entries[0] = "pipe" if "pipe" in mesh.axis_names else None
        return _drop_indivisible(P(*entries), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, stacked)


def zero3_stage_specs(stacked: PyTree, mesh: Mesh, rules=None,
                      dp_axes: Sequence[str] = ("pod", "data")):
    """ZeRO-3 layout for stage-stacked pipeline params: the
    ``pipeline_stage_specs`` layout with the data(+pod) axes added on the
    first shardable *weight* dim of every leaf, plus a parallel tree of
    gather dims for the executor's gather-on-use collectives.

    Returns ``(specs, dims)`` where ``dims`` holds, per leaf, the
    *stacked-tree* dim index carrying the DP shard, or ``-1`` when the leaf
    stays replicated across DP (tiny tensors with no divisible dim — the
    small-tensor fallback; the executor keeps the plain psum grad-reduce
    for those).  ``-1`` is a sentinel rather than None because None leaves
    vanish from pytrees.

    Dim choice skips the structural dims the executor indexes away before
    use: dim 0 is the pipe stage; for leaves under the top-level "layers"
    key dim 1 is the interleaving chunk (V) dim and dim 2 the scanned
    layer dim — the layer dim *is* shardable (the gather re-assembles it).
    """
    dp_axes = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1

    def choice(path, leaf):
        axes = _leaf_axes(path, leaf.ndim)
        base = param_partition_spec(axes, mesh, rules)
        entries = list(tuple(base) + (None,) * (leaf.ndim - len(tuple(base))))
        entries[0] = "pipe" if "pipe" in mesh.axis_names else None
        spec = _drop_indivisible(P(*entries), leaf.shape, mesh)
        entries = list(tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec))))
        if dp_size <= 1:
            return P(*entries), -1
        used = set()
        for e in entries:
            if e is not None:
                used.update((e,) if isinstance(e, str) else e)
        if any(a in used for a in dp_axes):
            return P(*entries), -1
        top = getattr(path[0], "key", getattr(path[0], "name", str(path[0])))
        min_dim = 2 if top == "layers" else 1
        for i in range(min_dim, leaf.ndim):
            e = entries[i]
            existing = () if e is None else (
                (e,) if isinstance(e, str) else tuple(e))
            ex = int(np.prod([mesh.shape[n] for n in existing])) \
                if existing else 1
            if leaf.shape[i] % (ex * dp_size) == 0:
                entries[i] = tuple(existing) + dp_axes
                return P(*entries), i
        return P(*entries), -1

    specs = jax.tree_util.tree_map_with_path(
        lambda p, l: choice(p, l)[0], stacked)
    dims = jax.tree_util.tree_map_with_path(
        lambda p, l: choice(p, l)[1], stacked)
    return specs, dims


def state_shardings(abstract_state, mesh: Mesh, zero: ZeROStage,
                    rules=None):
    """NamedSharding trees for a TrainState (params, master/m/v, step).

    params follow §3 TP/EP rules; {master, m, v} additionally DP-sharded for
    zero >= os; params DP-sharded for os+g+params.
    """
    from repro.optim.adamw import TrainState

    pspecs = param_specs(abstract_state.params, mesh, rules)
    shapes = jax.tree.map(lambda a: a.shape, abstract_state.params)

    def shard(spec_tree, with_dp):
        def one(spec, shape):
            s = add_dp_axes(spec, shape, mesh) if with_dp else spec
            return NamedSharding(mesh, s)
        return jax.tree.map(one, spec_tree, shapes)

    zp = zero == ZeROStage.OS_G_PARAMS
    zo = zero != ZeROStage.NONE
    return TrainState(
        step=NamedSharding(mesh, P()),
        params=shard(pspecs, zp),
        master=shard(pspecs, zo),
        m=shard(pspecs, zo),
        v=shard(pspecs, zo),
    )


def grad_shardings(abstract_params, mesh: Mesh, zero: ZeROStage, rules=None):
    """fp32 gradient-buffer shardings (DP-sharded for zero >= os+g)."""
    pspecs = param_specs(abstract_params, mesh, rules)
    shapes = jax.tree.map(lambda a: a.shape, abstract_params)
    with_dp = zero in (ZeROStage.OS_G, ZeROStage.OS_G_PARAMS)

    def one(spec, shape):
        s = add_dp_axes(spec, shape, mesh) if with_dp else spec
        return NamedSharding(mesh, s)

    return jax.tree.map(one, pspecs, shapes)
