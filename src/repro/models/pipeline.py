"""Pipeline-parallel partitioning of a Model into per-rank layer chunks.

The layer→chunk assignment is ``core.params.pp_stage_layers`` — the exact
split behind the paper's Table 4 — so the runtime executor, the per-stage
dry-run probes and the analytical model (``estimate_memory(stage=...)``,
``table4_stages``) can never disagree about which layers live where.  With a
pipeline *schedule* (``core.schedules``) a rank may hold several chunks:
plain ``1f1b`` keeps one contiguous stage per rank, Megatron-style
``interleaved`` assigns ``v`` virtual stages (rank r holds model chunks
``{r, pp+r, …}``), and ``dualpipe`` assigns each rank two mirrored stages
``(r, pp-1-r)`` with every stage *duplicated* across two ranks (DualPipe's
2× parameter cost).

Two views of the same partition are provided:

* **Heterogeneous chunk slices** (``stage_params_slice`` /
  ``chunk_params_slice`` + ``make_stage_fn`` / ``make_chunk_fn``): a chunk's
  true parameter subtree (embedding only with model chunk 0, final norm /
  head only with the last, its own contiguous dense/MoE sub-stacks) and a
  forward for exactly those layers.  Used by the dry-run to lower/compile
  each rank as its own program and read XLA's per-rank ``memory_analysis``
  — the numbers compared against ``estimate_memory(spec, cfg, stage=r,
  schedule=...)``.

* **Chunk-stacked (SPMD) layout** (``stack_pipeline_params`` /
  ``unstack_pipeline_grads`` + ``pipeline_stage_apply``): every layer leaf
  gains leading ``(pp, n_chunks, l_max)`` dims with the ``pp`` dim sharded
  over the ``pipe`` mesh axis, chunk layer stacks padded to the widest chunk
  (masked identity slots) and a *union* slot structure (a slot carries both
  the dense-MLP and MoE subtrees when the model mixes kinds; a per-slot
  flag selects).  Embedding / final-norm / head keep one row per rank, zero
  except on ranks whose chunks own them.  This is what the schedule-driven
  executor (``train.pipeline_loop``) runs under ``shard_map`` — one
  program, rank identity = ``lax.axis_index('pipe')``, the active chunk per
  tick read from the schedule's static tables.

The stacked layout trades memory for SPMD uniformity (padded slots, the
unused half of mixed dense/MoE slots, zero embed rows on interior ranks);
the per-rank dry-run path has no such padding, so memory validation always
uses the heterogeneous view.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.notation import AttentionKind, FamilyKind, ModelSpec
from repro.core.params import pp_stage_layers
from repro.parallel.axes import logical_constraint
from . import attention as A
from . import backend as B
from . import mla as M
from .layers import embed_apply, mlp_apply
from .moe import moe_forward
from .transformer import ModelOptions, _remat, stack_apply

PyTree = Any


def check_pipeline_supported(spec: ModelSpec) -> None:
    """The pipeline runtime covers the paper's training families: decoder-only
    dense and MoE transformers (MLA or GQA/MHA attention).  Recurrent, enc-dec
    and stub-frontend families keep the pp=1 path."""
    if spec.ssm is not None:
        raise NotImplementedError("pipeline runtime: SSM/hybrid unsupported")
    if spec.encoder is not None:
        raise NotImplementedError("pipeline runtime: enc-dec unsupported")
    if spec.family == FamilyKind.VLM:
        raise NotImplementedError("pipeline runtime: VLM frontend unsupported")
    if spec.attention == AttentionKind.NONE:
        raise NotImplementedError("pipeline runtime: attention-free unsupported")


@dataclasses.dataclass(frozen=True)
class StagePartition:
    """Layer→stage assignment plus the index/mask arrays both runtime views
    derive from it.  All arrays are numpy (static schedule data)."""

    pp: int
    n_layers: int
    n_dense: int                      # dense layers are global ids [0, n_dense)
    stages: Tuple[Tuple[int, ...], ...]
    l_max: int                        # widest stage (slot count of the SPMD view)
    idx: np.ndarray                   # (pp, l_max) global layer id; pads repeat
    mask: np.ndarray                  # (pp, l_max) f32: 1 real slot, 0 pad
    moe_flag: np.ndarray              # (pp, l_max) f32: 1 MoE layer, 0 dense
    stage_of: np.ndarray              # (n_layers,) stage owning each layer
    slot_of: np.ndarray               # (n_layers,) slot within that stage


def partition(spec: ModelSpec, pp: int) -> StagePartition:
    if not 1 <= pp <= spec.n_layers:
        raise ValueError(f"pp={pp} must be in [1, n_layers={spec.n_layers}]")
    stages = tuple(tuple(ls) for ls in pp_stage_layers(spec.n_layers, pp))
    n_dense = spec.n_layers - spec.n_moe_layers()
    l_max = max(len(ls) for ls in stages)
    idx = np.zeros((pp, l_max), np.int32)
    mask = np.zeros((pp, l_max), np.float32)
    moe_flag = np.zeros((pp, l_max), np.float32)
    stage_of = np.zeros(spec.n_layers, np.int32)
    slot_of = np.zeros(spec.n_layers, np.int32)
    for i, ls in enumerate(stages):
        for j in range(l_max):
            l = ls[j] if j < len(ls) else ls[-1]      # pads repeat a real layer
            idx[i, j] = l
            if j < len(ls):
                mask[i, j] = 1.0
                moe_flag[i, j] = float(l >= n_dense)
                stage_of[l] = i
                slot_of[l] = j
    return StagePartition(pp=pp, n_layers=spec.n_layers, n_dense=n_dense,
                          stages=stages, l_max=l_max, idx=idx, mask=mask,
                          moe_flag=moe_flag, stage_of=stage_of,
                          slot_of=slot_of)


@dataclasses.dataclass(frozen=True)
class ChunkedPartition:
    """Schedule-aware layer→(rank, chunk) assignment plus the index/mask
    arrays the chunk-stacked SPMD layout derives from it.  All arrays are
    numpy (static schedule data).  ``occurrences[l]`` lists every
    (rank, chunk, slot) holding global layer ``l`` — exactly one entry per
    layer except under dualpipe, where every layer lives on two ranks."""

    pp: int
    n_chunks: int                     # v, local chunks per rank
    n_stages: int                     # model chunks overall (pp*v or pp)
    n_layers: int
    n_dense: int
    schedule: str
    chunks: Tuple[Tuple[Tuple[int, ...], ...], ...]   # (pp, v) layer tuples
    placement: Tuple[Tuple[int, ...], ...]            # (pp, v) model chunk id
    l_max: int                        # widest chunk (slot count per chunk)
    idx: np.ndarray                   # (pp, v, l_max) global layer id
    mask: np.ndarray                  # (pp, v, l_max) f32: 1 real, 0 pad
    moe_flag: np.ndarray              # (pp, v, l_max) f32
    first_flag: np.ndarray            # (pp, v) f32: chunk is model chunk 0
    last_flag: np.ndarray             # (pp, v) f32: chunk is the last
    occurrences: Tuple[Tuple[Tuple[int, int, int], ...], ...]


def chunked_partition(spec: ModelSpec, pp: int, *, schedule: str = "1f1b",
                      n_chunks: int = 1) -> ChunkedPartition:
    """Partition for a pipeline schedule: model split into
    ``core.n_model_chunks`` contiguous pieces (same front-loaded Table-4
    rule as plain PP), placed per ``core.schedule_placement``."""
    from repro.core.activations import rank_chunk_layers
    from repro.core.schedules import (norm_chunks, n_model_chunks,
                                      schedule_placement)
    check_pipeline_supported(spec)
    v = norm_chunks(schedule, n_chunks)
    g = n_model_chunks(schedule, pp, v)
    if not 1 <= g <= spec.n_layers:
        raise ValueError(f"{g} model chunks need n_layers >= {g} "
                         f"(got {spec.n_layers})")
    chunks = rank_chunk_layers(spec, pp, schedule=schedule, n_chunks=v)
    placement = schedule_placement(schedule, pp, v)
    n_dense = spec.n_layers - spec.n_moe_layers()
    l_max = max(len(ls) for row in chunks for ls in row)
    idx = np.zeros((pp, v, l_max), np.int32)
    mask = np.zeros((pp, v, l_max), np.float32)
    moe_flag = np.zeros((pp, v, l_max), np.float32)
    first = np.zeros((pp, v), np.float32)
    last = np.zeros((pp, v), np.float32)
    occ: Dict[int, list] = {l: [] for l in range(spec.n_layers)}
    for r in range(pp):
        for c in range(v):
            ls = chunks[r][c]
            first[r, c] = float(placement[r][c] == 0)
            last[r, c] = float(placement[r][c] == g - 1)
            for j in range(l_max):
                l = ls[j] if j < len(ls) else ls[-1]  # pads repeat a layer
                idx[r, c, j] = l
                if j < len(ls):
                    mask[r, c, j] = 1.0
                    moe_flag[r, c, j] = float(l >= n_dense)
                    occ[l].append((r, c, j))
    return ChunkedPartition(
        pp=pp, n_chunks=v, n_stages=g, n_layers=spec.n_layers,
        n_dense=n_dense, schedule=schedule, chunks=chunks,
        placement=placement, l_max=l_max, idx=idx, mask=mask,
        moe_flag=moe_flag, first_flag=first, last_flag=last,
        occurrences=tuple(tuple(occ[l]) for l in range(spec.n_layers)))


# ---------------------------------------------------------------------------
# Heterogeneous view: true per-stage parameter subtrees + per-stage forward
# ---------------------------------------------------------------------------

def chunk_params_slice(params: PyTree, spec: ModelSpec,
                       layers: Tuple[int, ...], *, with_embed: bool,
                       with_head: bool) -> PyTree:
    """One contiguous layer chunk's parameters in the Model layout (keys
    kept so the §3 TP/ZeRO sharding rules in ``parallel.sharding`` apply
    unchanged).  ``with_embed``/``with_head`` attach the embedding / final
    norm + output head — owned by the first / last *model* chunk, which
    under multi-chunk schedules is a property of the chunk, not the rank."""
    check_pipeline_supported(spec)
    lo, hi = layers[0], layers[-1] + 1
    if list(layers) != list(range(lo, hi)):
        raise ValueError(f"chunk layers must be contiguous, got {layers}")
    nd = spec.n_layers - spec.n_moe_layers()
    out: Dict[str, Any] = {}
    if with_embed:
        out["embed"] = params["embed"]
    d_lo, d_hi = lo, min(hi, nd)
    if d_hi > d_lo:
        out["dense_layers"] = jax.tree.map(lambda a: a[d_lo:d_hi],
                                           params["dense_layers"])
    m_lo, m_hi = max(lo, nd) - nd, hi - nd
    if m_hi > max(m_lo, 0):
        out["moe_layers"] = jax.tree.map(lambda a: a[m_lo:m_hi],
                                         params["moe_layers"])
    if with_head:
        out["final_norm"] = params["final_norm"]
        if spec.tie_embeddings:
            out["embed"] = params["embed"]
        elif "head" in params:
            out["head"] = params["head"]
    return out


def stage_params_slice(params: PyTree, spec: ModelSpec, pp: int,
                       stage: int) -> PyTree:
    """Plain-1F1B view: stage ``stage``'s parameters (embedding on stage 0,
    final norm / head on the last stage)."""
    part = partition(spec, pp)
    return chunk_params_slice(params, spec, part.stages[stage],
                              with_embed=stage == 0, with_head=stage == pp - 1)


def make_chunk_fn(spec: ModelSpec, opts: ModelOptions,
                  layers: Tuple[int, ...], *, is_first: bool, is_last: bool):
    """fn(chunk_params, x, tokens) -> (out, aux) for one contiguous layer
    chunk.

    The first model chunk embeds ``tokens`` (``x`` is ignored); interior
    chunks transform the boundary activation ``x``; the last chunk returns
    vocab logits (callers compute the loss — the executor and the dry-run
    probes need different reductions).  Composing every chunk in model
    order is exactly ``Model.forward`` for the supported families.
    """
    check_pipeline_supported(spec)
    nd = spec.n_layers - spec.n_moe_layers()
    gemma = spec.name.startswith("gemma")
    window = spec.sliding_window

    def fn(chunk_params: PyTree, x: Optional[jnp.ndarray],
           tokens: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if is_first:
            x = embed_apply(chunk_params["embed"], tokens,
                            scale_by_dim=gemma, h=spec.h)
        b, s = x.shape[0], x.shape[1]
        x = logical_constraint(x, ("batch", "seq", "embed"))
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        aux = jnp.zeros((), jnp.float32)
        if "dense_layers" in chunk_params:
            x, a = stack_apply(chunk_params["dense_layers"], spec, opts, x,
                               positions, False, window=window)
            aux = aux + a
        if "moe_layers" in chunk_params:
            x, a = stack_apply(chunk_params["moe_layers"], spec, opts, x,
                               positions, True, window=window)
            aux = aux + a
        if is_last:
            x = B.rmsnorm(chunk_params["final_norm"], x, spec.norm_eps,
                          gemma_style=gemma, backend=B.resolve_backend(opts))
            if spec.tie_embeddings:
                logits = x @ chunk_params["embed"]["w"].T
            else:
                logits = x @ chunk_params["head"]["w"]
            logits = logical_constraint(logits, ("batch", "seq", "vocab"))
            return logits, aux
        return x, aux

    return fn


def make_stage_fn(spec: ModelSpec, opts: ModelOptions, pp: int, stage: int):
    """Plain-1F1B view of :func:`make_chunk_fn`: the forward of Table-4
    stage ``stage``.  With pp=1 this is exactly ``Model.forward``."""
    part = partition(spec, pp)
    return make_chunk_fn(spec, opts, part.stages[stage],
                         is_first=stage == 0, is_last=stage == pp - 1)


# ---------------------------------------------------------------------------
# Stage-stacked (SPMD) view: leading pp dim for shard_map over 'pipe'
# ---------------------------------------------------------------------------

def _take_layers(leaf: jnp.ndarray, index: np.ndarray) -> jnp.ndarray:
    flat = jnp.take(leaf, jnp.asarray(index.reshape(-1)), axis=0)
    return flat.reshape(index.shape + leaf.shape[1:])


def stack_pipeline_params(params: PyTree, spec: ModelSpec, pp: int, *,
                          schedule: str = "1f1b",
                          n_chunks: int = 1) -> PyTree:
    """Model params → chunk-stacked layout for the schedule.

    layers: union slot structure, leaves (pp, n_chunks, l_max, ...); pad
    slots repeat a real layer of the chunk (masked to identity at apply
    time) and the unused kind of a mixed dense/MoE slot holds a
    clipped-gather copy (never selected, so it receives exactly zero
    gradient).  embed/final_norm/head: (pp, ...) rows, zero except on ranks
    whose chunks own them (under dualpipe rank 0 and rank pp-1 each own an
    embedding *and* a head copy).
    """
    part = chunked_partition(spec, pp, schedule=schedule, n_chunks=n_chunks)
    nd = part.n_dense
    dense = params.get("dense_layers") or {}
    moe = params.get("moe_layers") or {}
    idx = part.idx
    idx_d = np.clip(idx, 0, max(nd - 1, 0))
    idx_m = np.clip(idx - nd, 0, max(part.n_layers - nd - 1, 0))

    layers: Dict[str, Any] = {}
    for k in dense:
        if k in moe:
            layers[k] = jax.tree.map(
                lambda a, b: _take_layers(jnp.concatenate([a, b], axis=0), idx),
                dense[k], moe[k])
        else:
            layers[k] = jax.tree.map(lambda a: _take_layers(a, idx_d), dense[k])
    for k in moe:
        if k not in dense:
            layers[k] = jax.tree.map(lambda a: _take_layers(a, idx_m), moe[k])

    has_first = part.first_flag.max(axis=1) > 0        # (pp,) rank owns chunk 0
    has_last = part.last_flag.max(axis=1) > 0
    emb = params["embed"]["w"]
    emb_st = jnp.zeros((pp,) + emb.shape, emb.dtype)
    fin = params["final_norm"]["scale"]
    fin_st = jnp.zeros((pp,) + fin.shape, fin.dtype)
    hd = params.get("head", {}).get("w")
    hd_st = jnp.zeros((pp,) + hd.shape, hd.dtype) if hd is not None else None
    for r in range(pp):
        if has_first[r] or (spec.tie_embeddings and has_last[r]):
            emb_st = emb_st.at[r].set(emb)
        if has_last[r]:
            fin_st = fin_st.at[r].set(fin)
            if hd_st is not None:
                hd_st = hd_st.at[r].set(hd)
    out: Dict[str, Any] = {"layers": layers,
                           "embed": {"w": emb_st},
                           "final_norm": {"scale": fin_st}}
    if hd_st is not None:
        out["head"] = {"w": hd_st}
    return out


def unstack_pipeline_grads(gstack: PyTree, params: PyTree, spec: ModelSpec,
                           pp: int, *, schedule: str = "1f1b",
                           n_chunks: int = 1) -> PyTree:
    """Chunk-stacked gradient pytree → the Model parameter layout.

    Every global layer's gradient is summed over its (rank, chunk, slot)
    occurrences — one under 1f1b/interleaved, two under dualpipe (both
    parameter copies saw different microbatches).  embed/final_norm/head
    rows are summed across ranks (rows on non-owning ranks are exactly
    zero: their outputs are never selected, so no gradient flows there)."""
    part = chunked_partition(spec, pp, schedule=schedule, n_chunks=n_chunks)
    nd = part.n_dense
    occ = part.occurrences
    r_idx = np.asarray([[o[0] for o in occ[l]] for l in range(part.n_layers)])
    c_idx = np.asarray([[o[1] for o in occ[l]] for l in range(part.n_layers)])
    s_idx = np.asarray([[o[2] for o in occ[l]] for l in range(part.n_layers)])

    def gather(leaf: jnp.ndarray) -> jnp.ndarray:
        # (n_layers, n_occurrences, ...) summed over occurrences
        return leaf[r_idx, c_idx, s_idx].sum(axis=1)

    dense = params.get("dense_layers") or {}
    moe = params.get("moe_layers") or {}
    out: Dict[str, Any] = {"dense_layers": {}, "moe_layers": {}}
    for k in dense:
        out["dense_layers"][k] = jax.tree.map(
            lambda a: gather(a)[:nd], gstack["layers"][k])
    for k in moe:
        out["moe_layers"][k] = jax.tree.map(
            lambda a: gather(a)[nd:], gstack["layers"][k])
    out["embed"] = {"w": gstack["embed"]["w"].sum(axis=0)}
    out["final_norm"] = {"scale": gstack["final_norm"]["scale"].sum(axis=0)}
    if "head" in params:
        out["head"] = {"w": gstack["head"]["w"].sum(axis=0)}
    return out


# ---------------------------------------------------------------------------
# SPMD stage apply (union slots, masked) — the executor's layer stack
# ---------------------------------------------------------------------------

def _slot_apply(p: PyTree, spec: ModelSpec, opts: ModelOptions,
                x: jnp.ndarray, positions: jnp.ndarray, mask: jnp.ndarray,
                moe_flag: jnp.ndarray, tp_axis: Optional[str] = None,
                sp: bool = False, ep: int = 1,
                dp_axes: Tuple[str, ...] = ()
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One union layer slot.  ``mask`` (scalar f32) turns pad slots into the
    identity; ``moe_flag`` selects the MoE vs dense-MLP branch when the model
    mixes kinds (only the selected branch receives gradient).

    ``tp_axis`` (the executor's 'model' mesh axis) switches on manual
    Megatron TP: ``spec`` must then be the TP-local view
    (``parallel.tp.tp_local_spec``) matching 'model'-sharded weights, and
    every block is bracketed by the f/g operators of ``parallel.tp`` —
    ``copy_to_tp`` where the replicated residual enters sharded compute,
    ``reduce_from_tp`` where partial block outputs rejoin it.

    ``sp`` (Megatron sequence parallelism, degree = tp) replaces the f/g
    pair with ğ and its dual: ``x`` arrives *seq-sharded* across
    ``tp_axis``, the norms run on the shard, ``gather_from_sp`` assembles
    the full sequence on entry to each TP region and ``scatter_to_sp``
    reduce-scatters block outputs back onto the shard.  The sharded token
    dim is always the second-to-last (the residual's seq, the MoE dispatch
    buffer's capacity, flat-token rows), hence ``ndim - 2`` below.

    ``ep`` (> 1 ⇒ == tp) switches the MoE branch to true expert
    parallelism over ``tp_axis``: routed expert weights arrive sharded on
    their *expert* dim and the dispatch is ``moe_forward``'s all-to-all
    token exchange instead of the replicated ETP buffer — ``tp_f``/``tp_g``
    then only bracket the shared expert (still ETP-sharded on its ff
    dim).

    ``dp_axes`` (the executor's data-parallel axes) makes the MoE aux loss
    the whole-microbatch value (``moe_forward``'s ``dp_axes``)."""
    from repro.parallel.tp import (copy_to_tp, gather_from_sp,
                                   reduce_from_tp, scatter_to_sp)
    gemma = spec.name.startswith("gemma")
    window = spec.sliding_window
    sp = bool(sp and tp_axis)
    if sp:
        tpf = lambda t: gather_from_sp(t, tp_axis, t.ndim - 2)
        tpg = lambda t: scatter_to_sp(t, tp_axis, t.ndim - 2)
    else:
        tpf = (lambda t: copy_to_tp(t, tp_axis)) if tp_axis else (lambda t: t)
        tpg = (lambda t: reduce_from_tp(t, tp_axis)) if tp_axis \
            else (lambda t: t)
    # ONE backend resolution per slot: the pallas kernels run on the
    # pre-sharded operands the f/g/ğ operators deliver — flash sees the
    # TP-local n_h/tp heads on the gathered full sequence, grouped_mlp the
    # (E/ep, C, h) local dispatch buffer (see models.backend's contract)
    backend = B.resolve_backend(opts)
    is_mla = spec.attention == AttentionKind.MLA
    attn_impl = B.resolve_attn_impl(opts, causal=True,
                                    window=None if is_mla else window)
    h1 = B.rmsnorm(p["ln1"], x, spec.norm_eps, gemma_style=gemma,
                   backend=backend)
    if is_mla:
        # MLA's replicated down-projections run redundantly on every shard;
        # the f operator sits on the compressed latents inside _towers.
        # Under SP the towers consume the *gathered* full-sequence view
        # (tpf(h1)) — the latents stay full-length on every shard, which is
        # why the paper's 2bs(d_cq+d_c) terms carry no /sp divisor — and
        # the latents must NOT carry copy_to_tp: the entry ğ's
        # reduce-scatter backward already sums the per-shard partial
        # cotangents, so a psum-bwd on the latents would double-count
        # (tp× gradients).  The tower weight grads are then head-partial
        # per shard; the executor's post-loop 'model' psum completes them
        # (train.pipeline_loop).
        lat_f = None if (sp or not tp_axis) else tpf
        mix = M.mla_forward(p["attn"], spec, tpf(h1) if sp else h1,
                            positions, impl=attn_impl, tpf=lat_f,
                            backend=backend)
    else:
        mix = A.gqa_forward(p["attn"], spec, tpf(h1), positions,
                            impl=attn_impl, window=window)
    mix = tpg(mix)
    x = x + mix * mask.astype(x.dtype)
    h2 = B.rmsnorm(p["ln2"], x, spec.norm_eps, gemma_style=gemma,
                   backend=backend)
    aux = jnp.zeros((), jnp.float32)
    has_mlp, has_moe = "mlp" in p, "moe" in p
    if has_moe:
        out = moe_forward(p["moe"], spec, h2,
                          capacity_factor=opts.capacity_factor,
                          router_impl=opts.router_impl,
                          tp_f=tpf if tp_axis else None,
                          tp_g=tpg if tp_axis else None,
                          sp_axis=tp_axis if sp else None,
                          ep=ep, ep_axis=tp_axis if ep > 1 else None,
                          dp_axes=dp_axes, backend=backend)
        sel = moe_flag.astype(x.dtype)
        delta = out.y * sel
        if has_mlp:
            delta = delta + tpg(mlp_apply(p["mlp"], spec, tpf(h2))) * (1 - sel)
        aux = out.aux_loss * moe_flag * mask
    elif has_mlp:
        delta = tpg(mlp_apply(p["mlp"], spec, tpf(h2)))
    else:
        delta = jnp.zeros_like(x)
    x = x + delta * mask.astype(x.dtype)
    return x, aux


def pipeline_stage_apply(layers_p: PyTree, spec: ModelSpec,
                         opts: ModelOptions, x: jnp.ndarray,
                         positions: jnp.ndarray, mask: jnp.ndarray,
                         moe_flag: jnp.ndarray,
                         tp_axis: Optional[str] = None,
                         sp: bool = False, ep: int = 1,
                         remat: bool = True,
                         dp_axes: Tuple[str, ...] = ()
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan this stage's l_max union slots.  ``layers_p`` leaves are
    (l_max, ...); ``mask``/``moe_flag`` are (l_max,).  With ``tp_axis`` the
    slots run manual TP; with ``sp`` additionally Megatron sequence
    parallelism — ``x`` is then the seq-sharded residual; with ``ep`` the
    MoE slots dispatch expert-parallel over the same axis; ``dp_axes``
    combines the MoE load-balance statistics across the data shards (see
    ``_slot_apply``).

    ``remat=False`` bypasses ``opts.recompute`` for this call: a vjp through
    the stage then stores the slot internals instead of recomputing them —
    the zb1p executor's B tick uses this (it runs the full vjp once, with
    no recompute replay, and parks the weight grads in the fp32 pending-dW
    stash for the deferred W flush; the replay it skips is exactly the
    compute zero-bubble trades stash memory for)."""

    def body(carry, inp):
        xc, aux = carry
        p_slot, m, f = inp
        xc, a = _slot_apply(p_slot, spec, opts, xc, positions, m, f, tp_axis,
                            sp, ep, dp_axes)
        return (xc, aux + a), None

    if remat:
        body = _remat(body, opts.recompute)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (layers_p, mask, moe_flag))
    return x, aux
