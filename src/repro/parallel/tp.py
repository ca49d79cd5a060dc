"""Manual tensor-parallel collectives for the pipeline executor.

The 3D executor (``train.pipeline_loop``) runs fully-manual ``shard_map``
over ('pipe', 'data', 'model'): nested GSPMD (``shard_map(auto=...)``) is
not usable on the jax versions this repo targets (the SPMD partitioner
rejects ``ppermute``/``with_sharding_constraint`` inside a partially-manual
body), so TP inside a rank is the classic Megatron construction with the
paired f/g operators spelled out:

* :func:`copy_to_tp`   — Megatron's *f*: identity forward, ``psum`` backward.
  Placed where a replicated activation *enters* a TP-sharded region (QKV
  input, MLP input, the logit projection input, MLA's compressed latents).
* :func:`reduce_from_tp` — Megatron's *g*: ``psum`` forward, identity
  backward.  Placed where partial results *leave* a TP region (attention
  out-projection, MLP/expert down-projection, vocab-parallel reductions).

With sequence parallelism on (``make_pipeline_train_step(..., sp=True)``,
degree tied to tp — the paper's SP column) the residual stream lives
*seq-sharded* across the same 'model' axis and the f/g pair is replaced by
its SP counterparts (Megatron's ğ and its dual):

* :func:`gather_from_sp` — ğ: all-gather along the sharded token dim
  forward (the TP region sees the full sequence), reduce-scatter backward
  (each shard gets the exact summed cotangent for its seq chunk).
* :func:`scatter_to_sp` — ğ's dual: reduce-scatter forward (the psum of
  ``reduce_from_tp`` fused with re-sharding the output sequence),
  all-gather backward.

LayerNorm inputs, residuals and boundary activations then cost 1/sp of
their replicated bytes — exactly the ``/sp`` divisor the paper's Table 10
applies to sequence-resident terms.  The price is Megatron's known grad
asymmetry: weights consumed *inside* the seq-sharded region (the norm
scales, the MoE router) see only their shard's tokens, so their local
gradients are seq-partial and the executor completes them with one
``psum`` over 'model' after the tick loop (``train.pipeline_loop``).

Why not plain ``jax.lax.psum``: under ``shard_map(check_vma=False)`` jax
cannot prove replication, so it transposes ``psum`` to another ``psum`` —
weight gradients come out ``tp``× too large.  The custom-vjp pairs encode
the replication facts we know by construction.  With f/g placed at every
replicated↔sharded boundary, *every* cotangent in the backward pass is the
exact global cotangent, so all weight gradients (sharded and replicated
leaves alike) are exact locally and need no further model-axis reduction.

Also here: the TP-local ``ModelSpec`` view (:func:`tp_local_spec`) the
executor feeds the unchanged model code (head/ff counts divided by tp so
reshapes line up with weight shards), the loud divisibility guard
(:func:`check_tp_supported`), and the vocab-parallel embedding / softmax
cross-entropy (:func:`embed_tp` / :func:`ce_sum_tp`) used by the first /
last model chunk.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.notation import AttentionKind, ModelSpec, tp_violations

TP_AXIS = "model"


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp(x: jnp.ndarray, axis: str = TP_AXIS) -> jnp.ndarray:
    """Identity forward; all-reduce (psum over ``axis``) backward."""
    return x


def _copy_fwd(x, axis):
    return x, None


def _copy_bwd(axis, _, ct):
    return (jax.lax.psum(ct, axis),)


copy_to_tp.defvjp(_copy_fwd, _copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tp(x: jnp.ndarray, axis: str = TP_AXIS) -> jnp.ndarray:
    """All-reduce (psum over ``axis``) forward; identity backward."""
    return jax.lax.psum(x, axis)


def _reduce_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _reduce_bwd(axis, _, ct):
    return (ct,)


reduce_from_tp.defvjp(_reduce_fwd, _reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pmax_stopgrad(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Cross-shard max with zero gradient — the log-sum-exp stabilizer
    (``pmax`` has no jax differentiation rule; the max-shift term cancels
    analytically, so a zero cotangent is exact)."""
    return jax.lax.pmax(x, axis)


def _pmax_fwd(x, axis):
    return jax.lax.pmax(x, axis), None


def _pmax_bwd(axis, _, ct):
    return (jnp.zeros_like(ct),)


_pmax_stopgrad.defvjp(_pmax_fwd, _pmax_bwd)


# ---------------------------------------------------------------------------
# Sequence-parallel boundary operators (Megatron's ğ and its dual)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_sp(x: jnp.ndarray, axis: str = TP_AXIS,
                   dim: int = 1) -> jnp.ndarray:
    """Megatron SP's ğ: all-gather the seq-sharded tensor along ``dim``
    forward (every shard sees the full sequence at the entry of a TP
    region); reduce-scatter the cotangent backward, which both sums the
    per-shard partial cotangents (the job ``copy_to_tp``'s psum-bwd did)
    and re-shards the sequence."""
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True)


def _gather_sp_fwd(x, axis, dim):
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True), None


def _gather_sp_bwd(axis, dim, _, ct):
    return (jax.lax.psum_scatter(ct, axis, scatter_dimension=dim,
                                 tiled=True),)


gather_from_sp.defvjp(_gather_sp_fwd, _gather_sp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scatter_to_sp(x: jnp.ndarray, axis: str = TP_AXIS,
                  dim: int = 1) -> jnp.ndarray:
    """ğ's dual: reduce-scatter along ``dim`` forward where partial results
    leave a TP region (``reduce_from_tp``'s psum fused with re-sharding the
    output sequence); all-gather the seq-sharded cotangent backward (every
    shard's sharded weights need the full-sequence cotangent)."""
    return jax.lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


def _scatter_sp_fwd(x, axis, dim):
    return jax.lax.psum_scatter(x, axis, scatter_dimension=dim,
                                tiled=True), None


def _scatter_sp_bwd(axis, dim, _, ct):
    return (jax.lax.all_gather(ct, axis, axis=dim, tiled=True),)


scatter_to_sp.defvjp(_scatter_sp_fwd, _scatter_sp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def pmean_sp(x: jnp.ndarray, axis: str = TP_AXIS) -> jnp.ndarray:
    """Cross-shard mean of per-shard token statistics (the MoE router's
    load-balance means under SP, EP or DP, where each shard routes a
    disjoint token chunk; ``axis`` may be a tuple of axes).  Forward
    ``pmean``; backward hands each shard ``ct / sp`` — the exact chain
    factor ∂mean/∂(shard summand), with no psum because
    the downstream consumer (the aux loss) is replicated, so every shard
    already carries the identical cotangent.  The seq-partial router
    gradients this produces are completed by the executor's post-loop
    'model'-axis psum (see ``train.pipeline_loop``)."""
    return jax.lax.pmean(x, axis)


def _pmean_sp_fwd(x, axis):
    return jax.lax.pmean(x, axis), None


def _pmean_sp_bwd(axis, _, ct):
    return (ct / jax.lax.psum(1, axis),)


pmean_sp.defvjp(_pmean_sp_fwd, _pmean_sp_bwd)


# ---------------------------------------------------------------------------
# ZeRO-3 gather-on-use boundary operator (all-gather over the DP axes)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_params(x: jnp.ndarray, axes=("data",), dim: int = 0) -> jnp.ndarray:
    """ZeRO-3's gather-on-use operator: the DP analogue of ğ, applied to
    *weights* instead of activations.  Forward all-gathers a rank's
    1/dp parameter shard along ``dim`` over the per-stage DP group
    (``axes`` — a tuple so ('pod','data') meshes work), so the tick's
    compute sees the full chunk weights; the gathered copy is a transient
    that dies with the tick.  Backward reduce-scatters the weight
    cotangent, which in one collective (a) sums the per-DP-replica grad
    contributions (the job the executor's post-loop data psum does for
    replicated leaves) and (b) re-shards the result onto the owner —
    so gradients, like the ZeRO-2 spec requires, only ever materialize
    shard-sized.  Same check_vma=False rationale as f/g: a plain
    all_gather would transpose to psum_scatter of *already-summed*
    cotangents only if jax could prove the forward input was unreplicated
    per-shard data, which it can't here."""
    return jax.lax.all_gather(x, axes, axis=dim, tiled=True)


def _gather_params_fwd(x, axes, dim):
    return jax.lax.all_gather(x, axes, axis=dim, tiled=True), None


def _gather_params_bwd(axes, dim, _, ct):
    return (jax.lax.psum_scatter(ct, axes, scatter_dimension=dim,
                                 tiled=True),)


gather_params.defvjp(_gather_params_fwd, _gather_params_bwd)


# ---------------------------------------------------------------------------
# Expert-parallel token boundary operators (a2a dispatch over 'model')
# ---------------------------------------------------------------------------
#
# With EP on (``make_pipeline_train_step(..., ep=tp)``) the MoE layer's
# routed experts live sharded on their *expert* dim across 'model' and the
# token exchange is an explicit ``lax.all_to_all`` (models.moe's EP path).
# Under SP the residual already arrives token-sharded, so EP composes with
# no extra operator; without SP the residual is replicated across 'model'
# and the EP region is bracketed by this pair — the token-dim analogue of
# copy_to_tp / reduce_from_tp, encoding the same replication facts the
# check_vma=False shard_map cannot prove:

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def shard_tokens_ep(x: jnp.ndarray, axis: str = TP_AXIS,
                    dim: int = 0) -> jnp.ndarray:
    """EP entry for a token tensor *replicated* across ``axis``: forward
    takes the rank's own 1/ep chunk along ``dim`` (a slice — no collective;
    every rank already holds the full tensor); backward all-gathers the
    per-chunk cotangents, which are exact per token (each token's entire
    downstream path runs on the one rank that owns it), so the assembled
    full cotangent is exact and replicated — the invariant every upstream
    consumer of the replicated residual assumes."""
    n = jax.lax.psum(1, axis)
    chunk = x.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(
        x, jax.lax.axis_index(axis) * chunk, chunk, axis=dim)


def _shard_ep_fwd(x, axis, dim):
    return shard_tokens_ep(x, axis, dim), None


def _shard_ep_bwd(axis, dim, _, ct):
    return (jax.lax.all_gather(ct, axis, axis=dim, tiled=True),)


shard_tokens_ep.defvjp(_shard_ep_fwd, _shard_ep_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def unshard_tokens_ep(x: jnp.ndarray, axis: str = TP_AXIS,
                      dim: int = 0) -> jnp.ndarray:
    """EP exit: all-gather the per-rank token chunks along ``dim`` forward
    (the combined MoE output rejoins the replicated residual); backward
    slices the rank's own chunk of the — replicated, exact — cotangent.
    A plain ``all_gather`` would transpose to ``psum_scatter``, which sums
    the ep identical cotangent copies (ep× gradients); the slice encodes
    the replication we know by construction."""
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True)


def _unshard_ep_fwd(x, axis, dim):
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True), None


def _unshard_ep_bwd(axis, dim, _, ct):
    n = jax.lax.psum(1, axis)
    chunk = ct.shape[dim] // n
    return (jax.lax.dynamic_slice_in_dim(
        ct, jax.lax.axis_index(axis) * chunk, chunk, axis=dim),)


unshard_tokens_ep.defvjp(_unshard_ep_fwd, _unshard_ep_bwd)


def check_ep_supported(spec: ModelSpec, tp: int, ep: int, *,
                       tokens_per_rank: Optional[int] = None) -> None:
    """Executor guard for expert parallelism: the a2a dispatch group is the
    whole 'model' axis, so the executor runs ``ep == tp`` (or 1 — the ETP
    path); the expert count must divide exactly (the expert-dim weight
    shard has no replicate-fallback) and without SP the per-rank token
    slice must tile the axis."""
    if ep == 1:
        return
    if not spec.is_moe:
        raise ValueError(f"{spec.name}: ep={ep} needs an MoE model")
    if ep != tp:
        raise ValueError(
            f"{spec.name}: ep={ep} != tp={tp}; the executor's a2a dispatch "
            f"group is the whole 'model' axis, so EP degree is tied to it "
            f"(grouped sub-axis a2a stays estimator-only)")
    if spec.moe.held != spec.moe.n_routed:
        raise NotImplementedError(
            f"{spec.name}: a layer that holds {spec.moe.held} of "
            f"{spec.moe.n_routed} experts runs without the exchange; its "
            f"all-to-all dispatch over the held share is not built")
    if spec.moe.n_routed % ep:
        raise ValueError(
            f"{spec.name}: ep={ep} does not divide n_routed="
            f"{spec.moe.n_routed}; the expert-dim shard requires exact "
            f"divisibility")
    if tokens_per_rank is not None and tokens_per_rank % ep:
        raise ValueError(
            f"{spec.name}: ep={ep} does not divide the per-rank token count "
            f"{tokens_per_rank} (b*s of one microbatch shard); the EP token "
            f"slice has no pad/replicate fallback")


# ---------------------------------------------------------------------------
# TP-local model view + loud divisibility guard
# ---------------------------------------------------------------------------

def check_tp_supported(spec: ModelSpec, tp: int) -> None:
    """Executor guard: manual TP assumes every sharded dim divides exactly
    (no silent replicate-fallback — the manual psums would double-count)."""
    bad = tp_violations(spec, tp)
    if bad:
        raise ValueError(
            f"{spec.name}: tp={tp} does not divide {', '.join(bad)}; the "
            f"pipeline executor's manual TP requires exact divisibility "
            f"(the GSPMD dry-run path replicates indivisible dims instead)")


def check_sp_supported(spec: ModelSpec, tp: int, seq_len: int) -> None:
    """Executor guard for sequence parallelism (degree tied to ``tp``):
    the token dim must divide exactly — ``all_gather``/``psum_scatter``
    have no replicate-fallback, and the analytic model's fallback
    (``core.activations._seq_shard_or_warn``) would silently diverge from
    a runtime that padded."""
    if tp <= 1:
        raise ValueError(
            f"{spec.name}: sequence parallelism ties its degree to TP "
            f"(Megatron SP); sp needs a 'model' mesh axis > 1, got tp={tp}")
    bad = tp_violations(spec, tp, sp=tp, seq_len=seq_len)
    if bad:
        raise ValueError(
            f"{spec.name}: sp={tp} not executable: {', '.join(bad)} "
            f"(the boundary all-gather/reduce-scatter pair requires exact "
            f"divisibility)")


def tp_local_spec(spec: ModelSpec, tp: int) -> ModelSpec:
    """The per-shard view of ``spec`` under TP degree ``tp``: head and ff
    counts divided so the unchanged model code's reshapes line up with the
    'model'-axis weight shards.  MoE experts shard their *ff* dim (the
    paper's ETP knob — every shard holds all experts, 1/tp of each), so the
    router and dispatch stay replicated and deterministic across shards."""
    if tp <= 1:
        return spec
    check_tp_supported(spec, tp)
    kw = dict(n_h=spec.n_h // tp)
    if spec.attention not in (AttentionKind.NONE, AttentionKind.MLA):
        kw["n_kv"] = spec.n_kv // tp
    if spec.h_ff:
        kw["h_ff"] = spec.h_ff // tp
    if spec.is_moe:
        kw["moe"] = dataclasses.replace(
            spec.moe, d_ff_expert=spec.moe.d_ff_expert // tp)
    return dataclasses.replace(spec, **kw)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding and cross-entropy (rows/columns on the TP axis)
# ---------------------------------------------------------------------------

def embed_tp(w_local: jnp.ndarray, tokens: jnp.ndarray, *,
             axis: str = TP_AXIS, scale_by_dim: bool = False,
             h: int = 0, sp: bool = False) -> jnp.ndarray:
    """Row-sharded embedding lookup: each shard gathers the rows it owns
    (shard i holds vocab rows [i·v_loc, (i+1)·v_loc)), zeros the rest, and
    the partial results are summed.  Backward scatters the exact cotangent
    into the owning shard's rows only.

    ``sp`` fuses the partial-sum with sequence sharding: the psum becomes
    a reduce-scatter over the token dim, so the residual stream leaves the
    embedding already seq-sharded; backward all-gathers the cotangent, so
    each shard's rows still receive the exact full-sequence gradient."""
    v_loc = w_local.shape[0]
    off = jax.lax.axis_index(axis) * v_loc
    idx = tokens - off
    ok = (idx >= 0) & (idx < v_loc)
    x = jnp.take(w_local, jnp.clip(idx, 0, v_loc - 1), axis=0)
    x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
    x = scatter_to_sp(x, axis, 1) if sp else reduce_from_tp(x, axis)
    if scale_by_dim:
        x = x * jnp.asarray(h ** 0.5, x.dtype)
    return x


def ce_sum_tp(logits_local: jnp.ndarray, tokens: jnp.ndarray,
              mask: jnp.ndarray, *, axis: str = TP_AXIS) -> jnp.ndarray:
    """Unnormalized next-token CE sum from column-sharded logits
    (``logits_local``: (b, s, v_loc) = shard's contiguous vocab columns).

    Distributed log-sum-exp: global max via ``pmax`` (stop-gradient, the
    standard stabilizer), exp-sums and the gold logit assembled with
    :func:`reduce_from_tp` so the backward pass hands each shard the exact
    cotangent for its local columns.  Matches the pp=1 ``_ce_sum`` to fp32
    round-off."""
    targets = tokens[:, 1:]
    lg = logits_local[:, :-1].astype(jnp.float32)
    v_loc = lg.shape[-1]
    gmax = _pmax_stopgrad(jnp.max(lg, axis=-1), axis)
    sumexp = reduce_from_tp(jnp.sum(jnp.exp(lg - gmax[..., None]), axis=-1),
                            axis)
    logz = jnp.log(sumexp) + gmax
    idx = targets - jax.lax.axis_index(axis) * v_loc
    ok = (idx >= 0) & (idx < v_loc)
    gold_l = jnp.take_along_axis(
        lg, jnp.clip(idx, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
    gold = reduce_from_tp(jnp.where(ok, gold_l, 0.0), axis)
    return jnp.sum((logz - gold) * mask)
