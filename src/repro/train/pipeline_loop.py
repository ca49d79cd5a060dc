"""Pipeline-parallel train step driven by a pluggable schedule.

``make_pipeline_train_step(model, cfg, mesh, schedule=..., n_chunks=...)``
builds one jit-able step that runs any of the four schedules in
``train.schedules`` / ``core.schedules`` — plain ``1f1b`` (the default,
PR 1's GPipe-fill + 1F1B steady state), Megatron-style ``interleaved``
virtual stages, the ``dualpipe`` bidirectional schedule, or the ``zb1p``
zero-bubble schedule (ZB-H1: the backward runs once at B, the per-layer
weight grads park in an fp32 pending stash and are applied on a dedicated
W tick — see the overlap-engine notes below) — over the ``pipe`` mesh
axis.  Arguments:

* ``model``: a ``models.build_model`` Model (decoder-only dense/MoE
  families; see ``models.pipeline.check_pipeline_supported``),
* ``cfg``: ``TrainConfig`` — ``cfg.n_micro`` microbatches per step
  (``interleaved`` requires ``n_micro % pp == 0``),
* ``mesh``: any of ``('pipe',)``, ``('pipe', 'data')`` or the full 3D
  ``('pipe', 'data', 'model')`` (``launch.mesh.make_production_mesh(pp=…)``);
  pp = mesh.shape['pipe'], tp = mesh.shape.get('model', 1),
* ``schedule``/``n_chunks``: schedule name and virtual stages per rank,
* ``zero``: ``ZeROStage`` — shard optimizer state (``os``), + gradients
  (``os+g``) across each stage's DP group (the 'data'(+'pod') axes).

One SPMD program (``shard_map``, fully manual over every mesh axis): every
device holds one rank's slice of the chunk-stacked parameters
(``models.pipeline.stack_pipeline_params``, leaves
``(pp, n_chunks, l, ...)`` per layer kind) — and, with a 'model' axis,
its 1/tp TP shard of them (``parallel.sharding.pipeline_stage_specs``:
Megatron head/column splits for attention and MLPs, expert-ff (ETP) splits for MoE,
vocab rows/columns for embedding/head) — and runs the same tick loop; rank
identity is ``lax.axis_index('pipe')``.  What happens at tick t — forward
or backward of which microbatch on which local chunk, and where boundary
tensors travel — is read from the schedule's static tables
(``train.schedules.build_exec_tables``), which re-time the canonical tick
stream under the executor's one-forward + one-backward (+ one W, for
schedules that split the backward) per tick capacity.

The tick body is an *overlap engine*, not a masked replay:

* **cond-gated compute** — each of the tick's F / B / W programs runs
  under ``lax.cond`` on its activity table, so a rank whose table row is
  idle (warmup, cooldown, drained) executes a no-op branch that just
  threads the carried buffers through: idle ticks cost ~0 instead of a
  full masked forward+backward.  The gate predicate depends only on the
  'pipe' rank, so it is uniform across 'data'/'model' and the collectives
  *inside* the branches (data psums, TP/SP operators, EP all-to-all)
  remain deadlock-free; the 'pipe' ppermutes — whose peers have
  *different* predicates — stay outside the conds.
* **true W-only ticks** — for ``zb1p`` the backward is the ZB-H1 split:
  B runs the fused chunk vjp once *without* slot checkpointing (the split
  stashes grads instead of recomputing activations, so the replay the
  checkpoint policy would pay is gone), retires dx and the shared
  embed/head/norm grads, and writes the per-layer fp32 pending-dW into a
  scan-carried stash slot (``b_sidx``); the dedicated W tick is a pure
  stash → accumulator flush (``w_sidx``) — cooldown fills with cheap W
  work exactly as ZB-H1 intends.  The stash ring depth is the interval
  colouring of the B→W pendency, whose peak
  ``core.schedules.zb_pending_peak`` the memory model prices.
* **async boundary comms** — each tick issues its forward-boundary
  ppermutes right after F and consumes them only after B/W (the transfer
  overlaps the backward), and the input-gradient computed by B rides the
  scan carry so its ppermute is issued at the *top of the next tick*,
  overlapping that tick's forward (the grad-receive tables are shifted
  one tick to match).  Inside the MoE chunk the EP all-to-all is likewise
  issued before — and consumed after — the shared expert's independent
  compute (``models.moe._moe_forward_ep``), the DualPipe dual-stream
  shape.

Boundary activations and activation-gradients move via
``lax.ppermute`` down-ring and (for dualpipe's reverse direction and
interleaved's virtual-stage wraparound) up-ring, landing in per-chunk slot
rings whose statically-coloured size is the executor's true in-flight bound
— the quantity ``core.schedule_in_flight`` models analytically.

Backward is *manual* (the tick loop is not differentiated): each rank keeps
its in-flight boundary inputs, recomputes the retiring chunk's forward, and
pulls gradients through ``jax.vjp`` with the downstream cotangent —
chunk-granular recompute, the standard JAX pipeline construction.  The
last model chunk's forward runs once, inside its backward tick: its output
has no consumer, so its F tick is dropped (``train.schedules.forward_runs``
checks that no boundary send reads it) and the vjp's primal pass supplies
the microbatch's loss, aux and MoE counts.  At pp = 1 that is every
forward, so the step emits no F branch at all; the step's ``fwd_fused``
metric counts the forwards served this way.  Under
``dualpipe`` every model chunk lives on two ranks (the schedule's 2×
parameter cost); ``unstack_pipeline_grads`` sums both copies' gradients.

Tensor parallelism runs *inside* each rank's chunk forward/backward.
Nested GSPMD is not viable on the targeted jax versions (the partitioner
rejects ``ppermute`` under a partially-auto ``shard_map``), so TP is the
explicit Megatron construction: the chunk forward sees the TP-local spec
(``parallel.tp.tp_local_spec`` — n_h/n_kv/h_ff/d_ff_expert divided by tp)
and the paired f/g operators of ``parallel.tp`` bracket every sharded
region (``copy_to_tp``: identity-fwd/psum-bwd where the replicated
residual enters sharded compute; ``reduce_from_tp``: psum-fwd/identity-bwd
where partial outputs leave it).  Embedding and head are vocab-parallel
(``embed_tp`` masked-gather rows; ``ce_sum_tp`` distributed log-sum-exp
over column-sharded logits).  With f/g at every boundary, every cotangent
in the manual backward is the exact global cotangent — so local weight
gradients (sharded and replicated leaves alike) are exact with no extra
model-axis reduction, and the boundary ``ppermute`` payloads stay
replicated across 'model', composing with TP untouched.

``sp=True`` adds Megatron-style sequence parallelism on the same 'model'
axis (degree = tp, the paper's SP column): the residual stream, norm
inputs and boundary activations live *seq-sharded* — (b, s/tp, h) per
device, the Table-10 ``/sp`` divisor made executor-real — and the f/g
pair is swapped for ğ and its dual (``gather_from_sp``: all-gather-fwd /
reduce-scatter-bwd on entry to every TP region; ``scatter_to_sp``:
reduce-scatter-fwd / all-gather-bwd on exit).  The embedding
reduce-scatters straight into the seq shard, the head gathers the
final-norm output before the column-sharded logits, MLA's replicated
latent towers consume the gathered view (latents stay full-length — the
paper's undivided 2bs(d_cq+d_c) terms), and MoE routes/dispatches each
shard's own token chunk with the dispatch buffer gathered over its
capacity dim (``models.moe.moe_forward(sp_axis=...)``).  Boundary
``ppermute`` payloads and the in-flight slot rings shrink to 1/tp of
their bytes.  One asymmetry is inherited from Megatron: weights consumed
*inside* the seq-sharded region — the ln1/ln2/final-norm scales and the
MoE router — see only their shard's tokens (their local grads are
seq-partial), and MLA's replicated latent towers run *without*
``copy_to_tp`` under SP (the entry ğ's reduce-scatter backward performs
the cross-shard sum; a psum-bwd on the latents would double-count), so
their weight grads are head-partial; the executor completes exactly
those leaves with a single ``psum`` over 'model' after the tick loop
(every other leaf stays exact-local as before).

``zero`` applies DeepSpeed-style state partitioning at the executor level
(previously dry-run-only): {master, m, v} — and for ``os+g`` the fp32
gradient buffers — carry ``with_sharding_constraint`` s from
``parallel.sharding.state_shardings``/``grad_shardings``, which extend
each leaf's §3 TP spec with the data(+pod) axes; since PP groups are
data-major, those axes are exactly the per-stage DP group, so each DP
shard holds 1/dp of its stage's optimizer bytes and XLA reduce-scatters
grads into the sharded AdamW update.

``os+g+params`` (ZeRO-3) goes one further: the bf16 *working* params
themselves live DP-sharded (``parallel.sharding.zero3_stage_specs``
extends the stacked per-stage specs with the data(+pod) axes on each
leaf's first shardable weight dim) and every F/B tick *gathers on use* —
``parallel.tp.gather_params``, the DP analogue of SP's ğ applied to
weights: forward all-gathers the tick's chunk slice (a transient the
memory model prices as ``gather_transient``), backward reduce-scatters
the weight cotangent, which sums the cross-DP grad contributions and
re-shards onto the owner in one collective.  The post-loop data psum is
skipped for exactly the gathered leaves (their grads arrive summed and
shard-sized); tiny leaves with no DP-divisible dim keep the replicated
layout and the psum path (DeepSpeed's small-tensor fallback).  The
gather/scatter live *inside* the cond-gated F/B branches — safe because
the gate predicate depends only on the 'pipe' rank, so it is uniform
across the 'data'(+'pod') axes the collectives run over.

Semantics match ``train.loop.make_train_step``: fp32 gradient accumulation
across microbatches, mean over n_micro, one AdamW update, loss metric
ce + aux_coef·aux per microbatch (``ModelSpec.aux_coef``).  ``TrainState`` keeps the pp=1 layout — grads
are unstacked back before the update — so optimizer, checkpointing and the
pp=1 path are untouched.  All four schedules reproduce the pp=1 step's
loss and post-update params to bf16-accumulation tolerance at
pp∈{2,4} × tp∈{1,2} × dp∈{1,2} (``tests/test_pipeline_1f1b.py``,
``tests/test_pipeline_3d.py``).

``ep=tp`` switches MoE layers from the default ETP dispatch (all experts
on every shard, expert-ff sharded, replicated routing) to true expert
parallelism on the same 'model' axis (paper §3.3): routed expert weights
live sharded on their *expert* dim (``(E/ep, h, h_E)`` per shard, full
hidden), each shard routes its own disjoint token chunk — the seq shard
under ``sp``; a ``shard_tokens_ep`` slice of the replicated residual
otherwise — buckets assignments by destination expert shard, and
exchanges ``(ep, C_send, h)`` send buffers via ``lax.all_to_all`` over
'model', runs the local ``(E/ep, C, h)`` grouped FFN and a2a's the
outputs back (``models.moe._moe_forward_ep``).  The shared expert stays
ETP (ff-sharded, every token through the f/g — or ğ/dual — pair), and
the router joins the post-loop 'model' psum: it is consumed inside the
token-sharded region, so its local grads are token-partial under EP
exactly as under SP.  The a2a dispatch group is the whole 'model' axis,
so the executor ties ``ep`` to ``tp`` (``parallel.tp.check_ep_supported``;
grouped sub-axis a2a remains estimator-only).

Scope notes: MoE aux uses the capacity dispatch; its load-balance means
are combined across the data shards (and, under ``sp``/``ep``, across
the token shards) so the aux value matches the unsharded step exactly.
The capacity itself stays per data shard, as in a real deployment.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import scopes
from repro.core.notation import AttentionKind
from repro.core.parallel_config import ZeROStage
from repro.models import backend as B
from repro.models.layers import embed_apply
from repro.models.model import Model
from repro.models.pipeline import (check_pipeline_supported,
                                   chunk_layers_apply, chunked_partition,
                                   stack_pipeline_params,
                                   unstack_pipeline_grads)
from repro.optim.adamw import TrainState, adamw_update
from repro.parallel.compat import shard_map
from repro.parallel.sharding import (grad_shardings, pipeline_stage_specs,
                                     state_shardings, zero3_stage_specs)
from repro.parallel.tp import (ce_sum_tp, check_ep_supported,
                               check_sp_supported, check_tp_supported,
                               copy_to_tp, embed_tp, gather_from_sp,
                               gather_params, tp_local_spec)
from repro.train.loop import TrainConfig, _split_micro
from repro.train.schedules import (build_exec_tables, forward_runs,
                                   make_schedule)

PyTree = Any

# Executor TP rules: like the §3 defaults, but experts shard their *ff* dim
# (ETP) instead of the expert dim (EP) — the router and capacity dispatch
# then run replicated and bit-identical on every 'model' shard, which the
# manual-collective construction requires (see parallel.tp).
_EXEC_TP_RULES = {"expert": None, "expert_ff": "model"}
# Executor EP rules (make_pipeline_train_step(..., ep=tp)): routed experts
# shard their *expert* dim across 'model' (the §3.3 default) and keep the
# full ff; the shared expert's 'ff' split is untouched (ETP).  Token
# exchange is then models.moe's explicit a2a dispatch.
_EXEC_EP_RULES = {"expert": "model", "expert_ff": None}


def _ce_mask(mask: Optional[jnp.ndarray], tokens: jnp.ndarray) -> jnp.ndarray:
    targets_shape = (tokens.shape[0], tokens.shape[1] - 1)
    if mask is None:
        return jnp.ones(targets_shape, jnp.float32)
    m = mask[:, 1:] if mask.shape == tokens.shape else mask
    return m.astype(jnp.float32)


def _ce_sum(logits: jnp.ndarray, tokens: jnp.ndarray,
            mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Unnormalized token-CE sum over the local batch shard (fp32), the
    summand of Model.loss's masked mean.  The gold logit is selected by a
    compare against the vocabulary index rather than gathered: the select
    fuses into the log-sum-exp's pass over the logits, and its backward
    into the softmax's, where a gather and its scatter keep whole fp32
    copies of the logits live in the backward tick, whose vjp returns
    this sum as the loss."""
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.sum(jnp.where(jnp.arange(lg.shape[-1]) == targets[..., None],
                             lg, 0.0), axis=-1)
    return jnp.sum((logz - gold) * _ce_mask(mask, tokens))


def _dyn(a: jnp.ndarray, i: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)


def make_pipeline_train_step(model: Model, cfg: TrainConfig, mesh: Mesh, *,
                             schedule: str = "1f1b", n_chunks: int = 1,
                             zero: ZeROStage = ZeROStage.NONE,
                             sp: bool = False, ep: int = 1,
                             gate_compute: bool = True):
    """Build the jit-able schedule-driven pipeline step for ``mesh`` (axes
    ('pipe'[, 'data'][, 'model'])); pp = mesh.shape['pipe'], TP degree =
    mesh.shape['model'].  Same contract as ``make_train_step``.  ``zero``
    shards optimizer state (and grads for ``os+g``) across the per-stage DP
    group via sharding constraints; callers keeping state resident across
    steps should ``device_put`` it with
    ``parallel.sharding.state_shardings(abstract_state, mesh, zero,
    rules=pipeline_loop._EXEC_TP_RULES)`` — the executor's ETP expert
    layout (identical to the default rules for non-MoE models).

    ``sp=True`` turns on Megatron sequence parallelism (degree tied to the
    'model' axis size; requires tp > 1 and ``seq_len % tp == 0`` — see the
    module docstring for the boundary-operator construction).  The
    parameter/optimizer layout and ZeRO constraints are unchanged: SP only
    re-shards activations, so it composes with any ``zero`` stage.

    ``ep=tp`` turns on true expert parallelism for MoE layers (paper
    §3.3): routed expert weights shard their *expert* dim across 'model'
    (``_EXEC_EP_RULES``) and dispatch is the explicit all-to-all token
    exchange — see the module docstring.  Requires an MoE model with
    ``n_routed % ep == 0`` and, without ``sp``, a per-rank token count
    divisible by ``ep``; the a2a group is the whole 'model' axis, so only
    ``ep in (1, tp)`` is executable.  Composes with any schedule, ``sp``
    and ``zero``; callers keeping state resident should use the
    ``_EXEC_EP_RULES`` layout in ``state_shardings``.

    ``gate_compute=False`` disables the ``lax.cond`` gating of the tick
    body: every tick then runs the full active-branch program and selects
    between it and the no-op result with ``jnp.where`` — the pre-overlap
    masked-executor cost model with the overlap engine's numerics.  The
    active branch's arithmetic is identical either way, so gated and
    ungated steps agree bit-for-bit; the flag exists for exactly that A/B
    check (``tests/test_zb_equivalence.py``) and for isolating cond-related
    compiler issues."""
    spec, opts = model.spec, model.opts
    check_pipeline_supported(spec)
    if "pipe" not in mesh.axis_names:
        raise ValueError("pipeline step needs a 'pipe' mesh axis "
                         "(launch.mesh.make_production_mesh(pp=...))")
    tp = mesh.shape.get("model", 1)
    tp_axis = "model" if tp > 1 else None
    check_tp_supported(spec, tp)
    sp = bool(sp)
    if sp and not tp_axis:
        raise ValueError(
            "sp=True needs a 'model' mesh axis of size > 1: Megatron SP "
            "ties the sequence-parallel degree to TP")
    ep = int(ep)
    check_ep_supported(spec, tp, ep)
    rules = _EXEC_EP_RULES if ep > 1 else _EXEC_TP_RULES
    spec_run = tp_local_spec(spec, tp)
    # ZeRO-3 (os+g+params): bf16 working params live DP-sharded
    # (zero3_stage_specs) and every F/B tick all-gathers the chunk's slice
    # on use via parallel.tp.gather_params, whose backward reduce-scatters
    # the weight cotangent — summing the cross-DP grad contributions and
    # re-sharding in one collective, so the post-loop data psum is skipped
    # for exactly the gathered leaves.
    zp = zero == ZeROStage.OS_G_PARAMS
    S = mesh.shape["pipe"]
    M = cfg.n_micro
    sched = make_schedule(schedule, S, M, n_chunks=n_chunks)
    tab = build_exec_tables(sched)
    part = chunked_partition(spec, S, schedule=schedule,
                             n_chunks=sched.n_chunks)
    V, T, XS, GS = sched.n_chunks, tab.T, tab.x_slots, tab.g_slots
    SS = tab.s_slots
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    gemma = spec.name.startswith("gemma")
    # per layer kind (S, V, l): the slots of each kind stack
    masks_all = {k.kind: jnp.asarray(k.mask) for k in part.slots}
    flags_all = {k.kind: jnp.asarray(k.moe_flag) for k in part.slots}
    aux_coef = spec.aux_coef
    first_all = jnp.asarray(part.first_flag)        # (S, V)
    last_all = jnp.asarray(part.last_flag)
    zb = schedule == "zb1p"
    tabs = {k: jnp.asarray(getattr(tab, k)) for k in (
        "f_act", "f_micro", "f_chunk", "f_xidx",
        "b_act", "b_micro", "b_chunk", "b_xidx", "b_gidx",
        "rfd_act", "rfd_idx", "rfu_act", "rfu_idx")
        + (("w_act", "w_micro", "w_chunk", "b_sidx", "w_sidx")
           if zb else ())}
    # The last model chunk's forward runs once, inside its backward tick's
    # vjp, which also yields its loss, aux and MoE counts: its F tick is
    # dropped (``forward_runs`` checks that no boundary send reads it).
    # Where no forward is left, as at pp = 1, no F branch is emitted.
    f_run = forward_runs(tab, part.last_flag)
    run_f = bool(f_run.any())
    tabs["f_run"] = jnp.asarray(f_run)
    # Grad arrivals are consumed one tick late: the input-gradient computed
    # at tick t rides the scan carry, its ppermute is issued at the TOP of
    # tick t+1 (so the ring transfer overlaps t+1's forward compute) and the
    # payload lands in the grad ring just before t+1's backward reads it.
    # The receive tables shift down one tick to match; visibility is
    # unchanged — a strict-previous-tick dependency means the earliest
    # consumer runs at t+1, which now reads the payload the moment it lands,
    # and slot-reuse stays safe (the write lands strictly after the previous
    # occupant's last read at tick <= t, exactly as the end-of-tick write
    # scheme guaranteed).
    _shift = lambda a: np.concatenate([np.zeros_like(a[:1]), a[:-1]], axis=0)
    for k in ("rgd_act", "rgd_idx", "rgu_act", "rgu_idx"):
        tabs[k] = jnp.asarray(_shift(getattr(tab, k)))
    # gate every permute on its own table: 1f1b/interleaved move forwards
    # down-ring and gradients up-ring only — permuting the unused payload
    # would double boundary traffic per tick
    use_f_down = bool(tab.fsend_down.any())
    use_f_up = bool(tab.fsend_up.any())
    use_b_down = bool(tab.bsend_down.any())
    use_b_up = bool(tab.bsend_up.any())

    def _psum(x, axes):
        return jax.lax.psum(x, axes) if axes else x

    def _run(stacked: PyTree, slot_masks: Dict[str, jnp.ndarray],
             slot_flags: Dict[str, jnp.ndarray], firsts: jnp.ndarray,
             lasts: jnp.ndarray, toks: jnp.ndarray,
             mmask: Optional[jnp.ndarray], gdims: Optional[PyTree] = None):
        """shard_map body: returns (chunk-stacked fp32 grads, loss_sum)."""
        d = jax.lax.axis_index("pipe")
        p = jax.tree.map(lambda a: jnp.squeeze(a, 0), stacked)
        local = lambda t: jax.tree.map(lambda a: a[0], t)
        smask, sflag = local(slot_masks), local(slot_flags)  # (V, l) each
        first_l, last_l = firsts[0], lasts[0]           # (V,) local
        _, b_loc, s = toks.shape
        s_loc = s // tp if sp else s      # SP: boundary tensors seq-sharded
        h = spec.h
        adt = p["embed"]["w"].dtype
        p_layers = p["layers"]
        p_shared = {k: v for k, v in p.items() if k != "layers"}

        # ZeRO-3 gather-on-use helpers.  ``gdims`` (static ints, -1 = leaf
        # stays replicated) indexes the *stacked* tree; the squeeze above
        # removes the pipe dim (-1) and ``layers_at`` the chunk dim (-1
        # more), so chunk-level layer leaves gather at dm-2 and shared
        # leaves at dm-1.  In the backward each gather transposes to a
        # psum_scatter of the weight cotangent, so dpl/dps/stash emerge
        # shard-shaped and already cross-DP-summed.
        if zp and gdims is not None and data_axes:
            gdl = gdims["layers"]
            gds = {k: v for k, v in gdims.items() if k != "layers"}
            gather_l = lambda pl: jax.tree.map(
                lambda a, dm: a if dm < 0 else
                gather_params(a, data_axes, dm - 2), pl, gdl)
            gather_s = lambda ps: jax.tree.map(
                lambda a, dm: a if dm < 0 else
                gather_params(a, data_axes, dm - 1), ps, gds)
            gdims_g = dict(gds, layers=gdl)
        else:
            gather_l = gather_s = lambda t: t
            gdims_g = None

        def chunk_fn(pl, ps, x_recv, tok, mm, c, remat=True):
            """Uniform per-chunk program: embed (selected when the chunk is
            the first model chunk), the chunk's kind stacks, head + local CE
            sum (meaningful on the last model chunk, zero-cotangent
            elsewhere).  Under TP the embedding is row-sharded and the
            logits column-sharded on 'model' (vocab-parallel CE); under SP
            the residual in and out of the slots — and the returned ``y`` —
            is the (b, s/tp, h) seq shard, and the head gathers the
            final-norm output before the column-sharded projection.
            ``remat=False`` (zb1p's split backward) bypasses the slot
            checkpointing so each half of the B/W split replays the chunk
            exactly once.  Also returns the slots' MoE ``[routed, kept]``
            counts (``chunk_layers_apply``)."""
            with jax.named_scope(scopes.EMBED):
                if tp_axis:
                    x0 = embed_tp(ps["embed"]["w"], tok, axis=tp_axis,
                                  scale_by_dim=gemma, h=spec.h, sp=sp)
                else:
                    x0 = embed_apply(ps["embed"], tok, scale_by_dim=gemma,
                                     h=spec.h)
                x = jnp.where(first_l[c] > 0.5, x0, x_recv)
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b_loc, s))
            y, aux, counts = chunk_layers_apply(
                pl, spec_run, opts, x, positions,
                {k: m[c] for k, m in smask.items()},
                {k: f[c] for k, f in sflag.items()},
                tp_axis=tp_axis, sp=sp, ep=ep, remat=remat,
                dp_axes=data_axes)
            with jax.named_scope(scopes.HEAD):
                z = B.rmsnorm(ps["final_norm"], y, spec.norm_eps,
                              gemma_style=gemma,
                              backend=B.resolve_backend(opts))
                w_out = ps["embed"]["w"].T if spec.tie_embeddings \
                    else ps["head"]["w"]
                if tp_axis:
                    zin = gather_from_sp(z, tp_axis, 1) if sp \
                        else copy_to_tp(z, tp_axis)
                    logits = zin @ w_out
                    ce = ce_sum_tp(logits, tok, _ce_mask(mm, tok),
                                   axis=tp_axis)
                else:
                    logits = z @ w_out
                    ce = _ce_sum(logits, tok, mm)
            return y, ce, aux, counts

        def micro_at(arr, m):
            return jax.lax.dynamic_index_in_dim(arr, m, 0, keepdims=False)

        def count_g(tok, mm):
            return _psum(jnp.sum(_ce_mask(mm, tok)), data_axes)

        def layers_at(c):
            return jax.tree.map(lambda a: _dyn(a, c), p_layers)

        def _cond(pred, on_fn, off_fn):
            """The overlap engine's gate: run ``on_fn`` only when the tick
            table says so (idle/warmup/cooldown ticks cost ~0 — the no-op
            branch just threads the carried buffers through unchanged, so
            both branches return identical pytree shapes and XLA aliases
            the buffers).  With ``gate_compute=False`` both branches run
            and ``jnp.where`` selects — the pre-overlap masked cost model
            with bit-identical active arithmetic (the A/B reference)."""
            if gate_compute:
                return jax.lax.cond(pred, on_fn, off_fn)
            on_v, off_v = on_fn(), off_fn()
            return jax.tree.map(
                lambda a_, b_: jnp.where(pred, a_, b_), on_v, off_v)

        def chunk_vjp(m, c, x_sv, dy, remat=True):
            """Gradients of microbatch ``m`` through chunk ``c``, and what
            the chunk's forward adds to the step's metrics.  The vjp's
            primal pass is the chunk's forward; on the last model chunk it
            is the only one (no F tick runs it), so that chunk's mean CE,
            aux and MoE counts, and one fused forward, are taken from it.
            On other chunks they are zero: their F ticks took them."""
            tok = micro_at(toks, m)
            mm = None if mmask is None else micro_at(mmask, m)

            def f(pl_, ps_, x_):
                y_, ce_, aux_, cnt_ = chunk_fn(gather_l(pl_), gather_s(ps_),
                                               x_, tok, mm, c, remat=remat)
                return (y_, ce_, aux_), cnt_

            (_, ce, aux), vjp_fn, cnt_c = jax.vjp(
                f, layers_at(c), p_shared, x_sv, has_aux=True)
            n_tok = jnp.maximum(count_g(tok, mm), 1.0)
            lastc = last_l[c]
            last = lastc > 0.5
            # output cotangents: the boundary grad ``dy`` (zeroed on the
            # last model chunk, whose ``y`` has no consumer), the CE mean
            # over the microbatch's target tokens (nonzero only on the
            # last chunk) and the aux weight (aux is the
            # whole-microbatch value on every data shard; its backward
            # hands each shard 1/data_size of the load-balance cotangent,
            # and the grads are psummed over the data axes below)
            grads = vjp_fn((jnp.where(last, jnp.zeros((), dy.dtype), dy),
                            lastc / n_tok, jnp.float32(aux_coef)))
            return grads, (lastc * _psum(ce, data_axes) / n_tok,
                           lastc * aux, jnp.where(last, cnt_c, 0),
                           last.astype(jnp.int32))

        def tick(carry, t):
            if zb:
                (xbuf, gbuf, gl, gsh, loss, aux_acc, cnt, nf, dx_c,
                 stash) = carry
            else:
                xbuf, gbuf, gl, gsh, loss, aux_acc, cnt, nf, dx_c = carry
            ring_dn = [(i, (i + 1) % S) for i in range(S)]
            ring_up = [(i, (i - 1) % S) for i in range(S)]

            def write(buf, act, idx, payload):
                i = idx[t, d]
                cur_v = _dyn(buf, i)
                val = jnp.where(act[t, d] > 0.5, payload, cur_v)
                return jax.lax.dynamic_update_index_in_dim(buf, val, i, 0)

            # -- issue: the PREVIOUS tick's input-gradient permutes.  The
            #    payload rode the scan carry, so the ring transfer is in
            #    flight while this tick's forward computes (ppermute stays
            #    outside the conds: it is a collective over 'pipe', where
            #    the gate predicates differ) -----------------------------
            if use_b_down:
                dx_dn = jax.lax.ppermute(dx_c, "pipe", ring_dn)
            if use_b_up:
                dx_up = jax.lax.ppermute(dx_c, "pipe", ring_up)

            # -- forward (cond-gated): the schedule's (micro, chunk), not
            #    the last model chunk, whose forward runs in its B tick;
            #    no loss: only the last chunk's CE counts --------------
            if run_f:
                fm = tabs["f_micro"][t, d]
                fc = tabs["f_chunk"][t, d]

                @jax.named_scope(scopes.TICK_F)
                def f_on():
                    x_in = _dyn(xbuf, tabs["f_xidx"][t, d])
                    tok_f = micro_at(toks, fm)
                    mm_f = None if mmask is None else micro_at(mmask, fm)
                    y_, _, aux_f, cnt_f = chunk_fn(
                        gather_l(layers_at(fc)), gather_s(p_shared), x_in,
                        tok_f, mm_f, fc)
                    return y_, aux_acc + aux_f, cnt + cnt_f

                def f_off():
                    return jnp.zeros((b_loc, s_loc, h), adt), aux_acc, cnt

                y, aux_acc, cnt = _cond(tabs["f_run"][t, d] > 0.5, f_on,
                                        f_off)

            # -- issue: this tick's forward-boundary permutes (consumed
            #    after the backward below — the transfer overlaps B/W) ----
            if use_f_down:
                y_dn = jax.lax.ppermute(y, "pipe", ring_dn)
            if use_f_up:
                y_up = jax.lax.ppermute(y, "pipe", ring_up)

            # -- consume: the grad payloads issued at the top of the tick
            #    land in the ring just before the backward reads them -----
            if use_b_down:
                gbuf = write(gbuf, tabs["rgd_act"], tabs["rgd_idx"], dx_dn)
            if use_b_up:
                gbuf = write(gbuf, tabs["rgu_act"], tabs["rgu_idx"], dx_up)

            # -- backward (cond-gated): retire (micro, chunk) -------------
            bm = tabs["b_micro"][t, d]
            bc = tabs["b_chunk"][t, d]

            fwd_acc = (loss, aux_acc, cnt, nf)

            def add_fwd(fwd):
                return tuple(a + b_ for a, b_ in zip(fwd_acc, fwd))

            if zb:
                # zb1p's ZB-H1 split: B runs the fused chunk vjp ONCE,
                # without slot checkpointing — the split stashes the fp32
                # pending-dW instead of recomputing activations, so the
                # replay the checkpoint policy would pay is gone (the
                # memory-for-time trade estimate_memory prices via
                # zb_pending_peak); on the last model chunk that vjp is
                # the chunk's only forward.  dx and the shared
                # embed/head/norm grads retire here; the per-layer dW
                # parks in its stash slot until the schedule's dedicated
                # W tick below.
                @jax.named_scope(scopes.TICK_B)
                def b_on():
                    x_sv = _dyn(xbuf, tabs["b_xidx"][t, d])
                    dy = _dyn(gbuf, tabs["b_gidx"][t, d])
                    (dpl, dps, dx_), fwd = chunk_vjp(bm, bc, x_sv, dy,
                                                     remat=False)
                    with jax.named_scope(scopes.GRAD_ACCUM):
                        pend = jax.tree.map(
                            lambda g_: g_.astype(jnp.float32), dpl)
                        stash_ = jax.tree.map(
                            lambda st, g_:
                            jax.lax.dynamic_update_index_in_dim(
                                st, g_, tabs["b_sidx"][t, d], 0),
                            stash, pend)
                        gsh_ = jax.tree.map(
                            lambda a, g_: a + g_.astype(jnp.float32), gsh,
                            dps)
                    return (stash_, gsh_, dx_) + add_fwd(fwd)

                def b_off():
                    return (stash, gsh,
                            jnp.zeros((b_loc, s_loc, h), adt)) + fwd_acc

                (stash, gsh, dx, loss, aux_acc, cnt, nf) = _cond(
                    tabs["b_act"][t, d] > 0.5, b_on, b_off)

                # -- weight-grad tick (cond-gated): the deferred half is a
                #    pure stash -> accumulator flush, so cooldown fills
                #    with cheap W work exactly as ZB-H1 intends (fp32 adds
                #    in microbatch order — the same reduction order as the
                #    fused path, just later) ------------------------------
                wc = tabs["w_chunk"][t, d]

                @jax.named_scope(scopes.TICK_W)
                @jax.named_scope(scopes.GRAD_ACCUM)
                def w_on():
                    pend = jax.tree.map(
                        lambda st: _dyn(st, tabs["w_sidx"][t, d]), stash)
                    cur = jax.tree.map(lambda a: _dyn(a, wc), gl)
                    upd = jax.tree.map(lambda a, g_: a + g_, cur, pend)
                    return jax.tree.map(
                        lambda a, u: jax.lax.dynamic_update_index_in_dim(
                            a, u, wc, 0), gl, upd)

                def w_off():
                    return gl

                gl = _cond(tabs["w_act"][t, d] > 0.5, w_on, w_off)
            else:
                @jax.named_scope(scopes.TICK_B)
                def b_on():
                    x_sv = _dyn(xbuf, tabs["b_xidx"][t, d])
                    dy = _dyn(gbuf, tabs["b_gidx"][t, d])
                    (dpl, dps, dx_), fwd = chunk_vjp(bm, bc, x_sv, dy)
                    with jax.named_scope(scopes.GRAD_ACCUM):
                        cur = jax.tree.map(lambda a: _dyn(a, bc), gl)
                        upd = jax.tree.map(
                            lambda a, g_: a + g_.astype(jnp.float32), cur,
                            dpl)
                        gl_ = jax.tree.map(
                            lambda a, u: jax.lax.dynamic_update_index_in_dim(
                                a, u, bc, 0), gl, upd)
                        gsh_ = jax.tree.map(
                            lambda a, g_: a + g_.astype(jnp.float32), gsh,
                            dps)
                    return (gl_, gsh_, dx_) + add_fwd(fwd)

                def b_off():
                    return (gl, gsh,
                            jnp.zeros((b_loc, s_loc, h), adt)) + fwd_acc

                gl, gsh, dx, loss, aux_acc, cnt, nf = _cond(
                    tabs["b_act"][t, d] > 0.5, b_on, b_off)

            # -- consume: this tick's forward-boundary payloads (issued
            #    before the backward) land in the rings ------------------
            if use_f_down:
                xbuf = write(xbuf, tabs["rfd_act"], tabs["rfd_idx"], y_dn)
            if use_f_up:
                xbuf = write(xbuf, tabs["rfu_act"], tabs["rfu_idx"], y_up)
            out = (xbuf, gbuf, gl, gsh, loss, aux_acc, cnt, nf, dx)
            return (out + (stash,) if zb else out), None

        @jax.named_scope(scopes.GRAD_ACCUM)
        def zeros_like_f32(tree):
            return jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                                tree)

        init = (jnp.zeros((V * XS, b_loc, s_loc, h), adt),
                jnp.zeros((V * GS, b_loc, s_loc, h), adt),
                zeros_like_f32(p_layers),
                zeros_like_f32(p_shared),
                jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32),
                jnp.zeros((2,), jnp.int32),          # MoE [routed, kept]
                jnp.zeros((), jnp.int32),            # fused forwards
                jnp.zeros((b_loc, s_loc, h), adt))    # in-flight dx carry
        if zb:
            # fp32 pending-dW stash: one chunk-shaped grad pytree per
            # stash slot, written at B, flushed at the dedicated W tick
            with jax.named_scope(scopes.GRAD_ACCUM):
                init = init + (jax.tree.map(
                    lambda a: jnp.zeros((V * SS,) + a.shape[1:],
                                        jnp.float32), p_layers),)
        fin, _ = jax.lax.scan(tick, init, jnp.arange(T))
        _, _, gl, gsh, loss, aux_acc, cnt, nf = fin[:8]

        with jax.named_scope(scopes.GRAD_SYNC):
            g = dict(gsh, layers=gl)
            if sp or ep > 1:
                # Token-sharded grad completion: weights applied *inside* a
                # token-sharded region accumulate grads from their shard's
                # tokens only; one psum over 'model' assembles the full
                # gradient for exactly those leaves.  Under SP that is the
                # norm scales, the MoE router and MLA's replicated latent
                # towers (which run without copy_to_tp under SP — the entry
                # ğ's reduce-scatter backward does the cross-shard sum — so
                # their grads are head-partial).  Under EP (with or without
                # SP) the router is consumed on each rank's disjoint token
                # chunk, so it needs the same completion; the expert weights
                # themselves do NOT — the a2a already delivered every rank
                # the full token set bound for its experts, so their local
                # grads are exact.  Every other leaf stays exact-local (the
                # boundary operators carry the cross-shard sums in their
                # backward rules) and must NOT be psummed — that would scale
                # it by tp.
                def complete(lay):
                    lay = dict(lay)
                    if sp:
                        for k in ("ln1", "ln2"):
                            lay[k] = jax.lax.psum(lay[k], tp_axis)
                    if "moe" in lay:
                        lay["moe"] = dict(
                            lay["moe"],
                            router=jax.lax.psum(lay["moe"]["router"],
                                                tp_axis))
                    if sp and spec.attention == AttentionKind.MLA:
                        attn_g = dict(lay["attn"])
                        for k in ("w_dq", "w_dkv", "w_kr", "q_norm",
                                  "kv_norm"):
                            if k in attn_g:
                                attn_g[k] = jax.lax.psum(attn_g[k], tp_axis)
                        lay["attn"] = attn_g
                    return lay
                g = dict(g, layers={kind: complete(lay) for kind, lay
                                    in g["layers"].items()})
                if sp:
                    g = dict(g, final_norm=jax.lax.psum(g["final_norm"],
                                                        tp_axis))
            if gdims_g is not None:
                # ZeRO-3: gathered leaves' grads were already cross-DP-summed
                # (and re-sharded) by gather_params' backward psum_scatter —
                # a data psum here would double-count them.  Replicate-fallback
                # leaves (dm < 0) still need the sum.
                g = jax.tree.map(
                    lambda a, dm: (_psum(a, data_axes) if dm < 0 else a)[None],
                    g, gdims_g)
            else:
                g = jax.tree.map(lambda a: _psum(a, data_axes)[None], g)
        loss_sum = jax.lax.psum(loss + aux_coef * aux_acc, "pipe")
        # the MoE counts of every chunk forward: each pipe rank holds other
        # layers, each data shard other samples and, where the tokens are
        # sharded over 'model' (SP, EP), each model shard other tokens
        cnt_axes = ("pipe",) + data_axes + (
            (tp_axis,) if tp_axis and (sp or ep > 1) else ())
        return (g, loss_sum, jax.lax.psum(cnt, cnt_axes),
                jax.lax.psum(nf, "pipe"))

    data_size = 1
    for a in data_axes:
        data_size *= mesh.shape[a]

    def _zero_constrain(st: TrainState) -> TrainState:
        """ZeRO residency: pin {master, m, v} to their per-stage-DP-group
        shardings (state keeps the pp=1 layout; the 'data'(+'pod') axes of
        this mesh *are* the within-stage DP group because PP carves the
        leading 'pipe' axis out of data)."""
        sh = state_shardings(st, mesh, zero, rules=rules)
        wsc = jax.lax.with_sharding_constraint
        st = st._replace(master=wsc(st.master, sh.master),
                         m=wsc(st.m, sh.m), v=wsc(st.v, sh.v))
        if zp:
            # ZeRO-3: the bf16 working params are DP-sharded at rest too
            st = st._replace(params=wsc(st.params, sh.params))
        return st

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]
             ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        micro = _split_micro(batch, M)
        toks = micro["tokens"]
        if toks.shape[1] % data_size:
            raise ValueError(
                f"micro-batch size {toks.shape[1]} must divide the data axes "
                f"(size {data_size})")
        if sp:
            check_sp_supported(spec, tp, toks.shape[2])
        if ep > 1 and not sp:
            # the EP entry slices each rank's replicated (b_loc·s) token
            # set into ep chunks; under sp the residual already arrives
            # token-sharded and no slice happens
            check_ep_supported(
                spec, tp, ep,
                tokens_per_rank=(toks.shape[1] // data_size) * toks.shape[2])
        if zero != ZeROStage.NONE:
            with jax.named_scope(scopes.OPTIMIZER):
                state = _zero_constrain(state)
        with jax.named_scope(scopes.STAGE_STACK):
            stacked = stack_pipeline_params(state.params, spec, S,
                                            schedule=schedule, n_chunks=V)
        if zp and data_axes:
            stage_specs, gdims = zero3_stage_specs(stacked, mesh,
                                                   rules=rules)
        else:
            stage_specs = pipeline_stage_specs(stacked, mesh, rules=rules)
            gdims = None
        dspec = tuple(data_axes) if data_axes else None
        margs = (toks,)
        mspecs = (P(None, dspec, None),)
        if "mask" in micro:
            margs += (micro["mask"],)
            mspecs += (P(None, dspec, *(None,) * (micro["mask"].ndim - 2)),)

        def inner(stacked_l, masks_l, flags_l, firsts_l, lasts_l, toks_l,
                  *rest):
            return _run(stacked_l, masks_l, flags_l, firsts_l, lasts_l,
                        toks_l, rest[0] if rest else None, gdims=gdims)

        slot_spec = {k: P("pipe", None, None) for k in masks_all}
        g_st, loss_sum, counts, fused = shard_map(
            inner, mesh=mesh,
            in_specs=(stage_specs, slot_spec, slot_spec,
                      P("pipe", None), P("pipe", None)) + mspecs,
            out_specs=(stage_specs, P(), P(), P()),
        )(stacked, masks_all, flags_all, first_all, last_all, *margs)
        with jax.named_scope(scopes.STAGE_STACK):
            grads = unstack_pipeline_grads(g_st, state.params, spec, S,
                                           schedule=schedule, n_chunks=V)
        if zero in (ZeROStage.OS_G, ZeROStage.OS_G_PARAMS):
            # ZeRO-2: reduce-scatter the fp32 accumulation buffers onto the
            # per-stage DP group before the (sharded) optimizer update
            with jax.named_scope(scopes.GRAD_SYNC):
                grads = jax.lax.with_sharding_constraint(
                    grads, grad_shardings(state.params, mesh, zero,
                                          rules=rules))
        with jax.named_scope(scopes.OPTIMIZER):
            grads = jax.tree.map(lambda a: a / M, grads)
            new_state, opt_metrics = adamw_update(state, grads, cfg.adamw)
            if zero != ZeROStage.NONE:
                new_state = _zero_constrain(new_state)
        # whole-step MoE assignments (int32; 0 for a dense model): routed,
        # and kept by the capacity (under EP: the send bucket and the
        # receiving rank's capacity), once per chunk forward; and the
        # chunk forwards that a backward tick's vjp ran in place of an F
        # tick (int32; n_micro at pp = 1)
        metrics = {"loss": loss_sum / M, **opt_metrics,
                   "moe_routed": counts[0], "moe_kept": counts[1],
                   "fwd_fused": fused}
        return new_state, metrics

    return step
