"""Activation-memory model (paper §5, Table 10) + extensions.

The paper derives per-layer activation bytes for the MLA and MoE blocks of
DeepSeek-v3 under TP2@SP2@CP1 with recomputation None / Full.  We implement
those formulas symbolically in (b, s, tp, sp, cp, ep, etp) so they reproduce
Table 10 exactly at the paper's settings, and extend the same accounting
discipline to the other assigned families (GQA/MQA attention, dense
SwiGLU/GeGLU/GELU MLPs, RWKV6 recurrence, hybrid layers, enc-dec).

Conventions (paper §5):
* bf16 activations → 2 bytes/value; masks/probabilities counted at the
  byte width the paper uses (5 b n_h s² = 2+2+1: scores, softmax, mask).
* SP divides sequence-resident tensors outside the TP regions; TP divides
  head/channel-sharded tensors; CP divides the sequence everywhere.
* MoE expert-side tensors use the balanced-routing estimate
  E_token = b·s·N_r / N  (paper §5.2), with N/EP local experts per rank and
  shared experts processing the full b·s tokens.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Dict, Optional

from .notation import AttentionKind, FamilyKind, MlpKind, ModelSpec
from .parallel_config import ParallelConfig, RecomputePolicy

# attn_impl values that never materialise the resident s² score buffers:
# the tiled kernel recomputes scores inside each layer's backward, so the
# 5·b·n_h·s² term drops from the activation stash.  "chunked" (the jnp
# lax.scan online-softmax) is deliberately NOT here — its scan residuals
# still store O(s²) under AD.
FLASH_ATTN_IMPLS = ("flash", "pallas")


def _shard_or_warn(dim: int, tp: int, what: str) -> int:
    """Effective TP divisor of a *channel/fused*-sharded dimension (qkv
    columns, ff hidden, ssm channels): ``tp`` when it divides exactly,
    else 1 (the tensor is replicated — same fallback as ``params._shard``)
    with a loud warning.  Before this guard the formulas silently
    floor-divided, which under-counted indivisible combos."""
    if tp <= 1:
        return 1
    if dim % tp == 0:
        return tp
    warnings.warn(
        f"tp={tp} does not divide {what}={dim}; modeling this tensor as "
        f"TP-replicated (the runtime's indivisible-dim fallback)",
        RuntimeWarning, stacklevel=3)
    return 1


def _seq_shard_or_warn(s: int, sp: int, what: str = "s") -> int:
    """Effective SP divisor of a *sequence-resident* tensor (the residual
    stream, norm inputs, boundary activations outside the TP regions):
    ``sp`` when it divides the (CP-local) sequence exactly, else 1 — the
    tensor stays SP-replicated — with a loud warning.  Before this guard
    the formulas silently floor-divided ``// sp``, under-counting
    indivisible sequence lengths; the executor's hard check is
    ``parallel.tp.check_sp_supported`` via ``notation.tp_violations(...,
    sp=..., seq_len=...)``."""
    if sp <= 1:
        return 1
    if s % sp == 0:
        return sp
    warnings.warn(
        f"sp={sp} does not divide {what}={s}; modeling sequence-resident "
        f"tensors as SP-replicated (the executor rejects this combo "
        f"outright — parallel.tp.check_sp_supported)",
        RuntimeWarning, stacklevel=3)
    return 1


def _head_shard_or_warn(n_heads: int, tp: int, what: str) -> int:
    """Effective TP divisor of a *head-count*-sharded tensor (the s²
    score/softmax buffers, laid out (b, n_h, s, s)): heads split evenly at
    most gcd(n_h, tp) ways.  The fused qkv columns may still shard the
    full ``tp`` ways (sub-head column splits — e.g. n_h=12 columns on a
    16-wide model axis), so this clamp applies only to the head-indexed
    tensors; warn loudly whenever the degree degrades."""
    if tp <= 1:
        return 1
    if n_heads % tp == 0:
        return tp
    g = math.gcd(n_heads, tp)
    warnings.warn(
        f"tp={tp} does not divide {what}={n_heads}; head-sharded score "
        f"tensors split at most gcd={g} ways (fused qkv columns still "
        f"shard tp ways when divisible)",
        RuntimeWarning, stacklevel=3)
    return g


@dataclasses.dataclass(frozen=True)
class ActivationBreakdown:
    attn: int          # MLA / GQA attention block
    mlp: int           # dense-MLP or MoE block (incl. router)
    ssm: int           # recurrent path
    per_layer: int     # attn + mlp + ssm (one layer)

    def scaled(self, n_layers: int) -> int:
        return self.per_layer * n_layers


# ---------------------------------------------------------------------------
# MLA (paper §5.1)
# ---------------------------------------------------------------------------

def mla_activation_bytes(spec: ModelSpec, b: int, s: int, *, tp: int, sp: int,
                         cp: int, recompute: RecomputePolicy,
                         attn_impl: str = "naive") -> int:
    """One layer of MLA activations (bytes).

    AC None (paper, TP@SP):
      M1 = 4bsh/sp + 2bs(d_cq+d_c) + 4bs(d_h+d_hr)n_h/tp + 2bs d_h n_h/tp
           + 5 b n_h s^2/tp + 2bs d_h n_h/tp + bsh/sp
    The 2bs(d_cq+d_c) latent tensors are NOT divided by sp because the down
    projections are replicated (paper); without a query LoRA (``d_cq``
    None) there is no query latent and the term is 2bs·d_c.  AC Full:
    2bsh/sp.

    ``attn_impl`` in ``FLASH_ATTN_IMPLS`` drops exactly the 5·b·n_h·s²
    score/softmax/mask term at AC-None — the tiled kernel keeps the s²
    blocks transient inside each layer's fwd/bwd.  At SELECTIVE the term
    is already gone, so flash changes nothing (no double subtraction).
    """
    if spec.attention == AttentionKind.NONE:
        return 0
    m = spec.mla
    s = s // cp
    sp = _seq_shard_or_warn(s, sp)
    if recompute == RecomputePolicy.FULL:
        return 2 * b * s * spec.h // sp
    tp_c = _shard_or_warn(spec.n_h * m.d_h, tp, "n_h*d_h")
    scores = 5 * b * spec.n_h * s * s \
        // _head_shard_or_warn(spec.n_h, tp, "n_h")
    none_total = (
        4 * b * s * spec.h // sp
        + 2 * b * s * ((m.d_cq or 0) + m.d_c)
        + 4 * b * s * (m.d_h + m.d_hr) * spec.n_h // tp_c
        + 2 * b * s * m.d_v * spec.n_h // tp_c
        + scores
        + 2 * b * s * m.d_v * spec.n_h // tp_c
        + b * s * spec.h // sp
    )
    if recompute == RecomputePolicy.SELECTIVE \
            or attn_impl in FLASH_ATTN_IMPLS:
        # drop the O(s^2) score/softmax/mask tensors (flash-style)
        return none_total - scores
    return none_total


# ---------------------------------------------------------------------------
# MoE linear (paper §5.2)
# ---------------------------------------------------------------------------

def moe_activation_bytes(spec: ModelSpec, b: int, s: int, *, sp: int, cp: int,
                         ep: int, recompute: RecomputePolicy) -> int:
    """One MoE layer's activations (bytes), paper §5.2.

    AC None (SP@EP@ETP1):
      M1 = 4bsh/sp + 4bsN + 2bsN_r
           + n_local * (3 E_token h + 8 E_token h_E)
           + N_s * (3bsh + 8bs h_E)
    AC Full: bsh + 2 b s N_r  (input + router outputs kept).
    """
    e = spec.moe
    s = s // cp
    sp = _seq_shard_or_warn(s, sp)
    if recompute == RecomputePolicy.FULL:
        return b * s * spec.h + 2 * b * s * e.n_active
    n_local = e.held // _shard_or_warn(e.held, ep, "n_routed (EP)")
    e_token = b * s * e.n_active / e.n_routed
    routed = n_local * (3 * e_token * spec.h + 8 * e_token * e.d_ff_expert)
    shared = e.n_shared * (3 * b * s * spec.h + 8 * b * s * e.d_ff_expert)
    total = (4 * b * s * spec.h // sp
             + 4 * b * s * e.n_routed
             + 2 * b * s * e.n_active
             + int(routed) + shared)
    if recompute == RecomputePolicy.SELECTIVE:
        # recompute expert FFN internals, keep dispatch/router/output
        total -= int(routed) + shared
        total += int(n_local * 2 * e_token * spec.h) + e.n_shared * 2 * b * s * spec.h
    return total


# ---------------------------------------------------------------------------
# Extensions: GQA attention, dense MLP, SSM (same accounting discipline)
# ---------------------------------------------------------------------------

def gqa_activation_bytes(spec: ModelSpec, b: int, s: int, *, tp: int, sp: int,
                         cp: int, recompute: RecomputePolicy,
                         attn_impl: str = "naive") -> int:
    """Standard MHA/GQA/MQA attention block, naive-softmax accounting to
    mirror the paper's 5 b n_h s² convention.  ``attn_impl`` in
    ``FLASH_ATTN_IMPLS`` drops the s² term at AC-None (see
    ``mla_activation_bytes``)."""
    s = s // cp
    sp = _seq_shard_or_warn(s, sp)
    if recompute == RecomputePolicy.FULL:
        return 2 * b * s * spec.h // sp
    d = spec.d_head
    tp_c = _shard_or_warn(spec.n_h * d, tp, "n_h*d_head")
    # kv-head clamp: K/V shard at most n_kv ways (min(tp, n_kv) — the same
    # clamp kv_cache_bytes applies on the decode path), degrading to
    # gcd when the clamped degree doesn't divide n_kv
    kv_shard = min(tp, spec.n_kv)
    if kv_shard > 1 and spec.n_kv % kv_shard:
        kv_shard = _head_shard_or_warn(spec.n_kv, kv_shard, "n_kv")
    scores = 5 * b * spec.n_h * s * s \
        // _head_shard_or_warn(spec.n_h, tp, "n_h")
    total = (
        2 * b * s * spec.h // sp                      # norm output (QKV input)
        + 2 * b * s * spec.n_h * d // tp_c            # Q
        + 2 * 2 * b * s * spec.n_kv * d // kv_shard   # K, V
        + scores
        + 2 * b * s * spec.n_h * d // tp_c            # attn context
        + b * s * spec.h // sp                        # o-proj output grad buffer
    )
    if recompute == RecomputePolicy.SELECTIVE \
            or attn_impl in FLASH_ATTN_IMPLS:
        total -= scores
    return total


def dense_mlp_activation_bytes(spec: ModelSpec, b: int, s: int, *, tp: int,
                               sp: int, cp: int,
                               recompute: RecomputePolicy) -> int:
    s = s // cp
    sp = _seq_shard_or_warn(s, sp)
    if recompute == RecomputePolicy.FULL:
        return 2 * b * s * spec.h // sp
    tp = _shard_or_warn(spec.h_ff, tp, "h_ff") if spec.h_ff else 1
    inp = 2 * b * s * spec.h // sp
    if spec.mlp in (MlpKind.SWIGLU, MlpKind.GEGLU):
        hidden = 3 * 2 * b * s * spec.h_ff // tp      # gate, up, gated product
    else:
        hidden = 2 * 2 * b * s * spec.h_ff // tp      # fc1 out, act out
    return inp + hidden


def ssm_activation_bytes(spec: ModelSpec, b: int, s: int, *, tp: int, sp: int,
                         cp: int, recompute: RecomputePolicy) -> int:
    """RWKV6/Mamba-style recurrent block.  The O(1)-in-s state is b·n_h·d·d;
    training stores the r/k/v/g/w projections (O(s)) unless recomputed."""
    if spec.ssm is None:
        return 0
    ss = spec.ssm
    s = s // cp
    sp = _seq_shard_or_warn(s, sp)
    d = spec.h * ss.ssm_expand
    state = 2 * b * ss.n_ssm_heads * (d // max(ss.n_ssm_heads, 1)) * ss.state_dim
    if recompute == RecomputePolicy.FULL:
        return 2 * b * s * spec.h // sp + state
    tp = _shard_or_warn(d, tp, "ssm channel dim")
    proj = 5 * 2 * b * s * d // tp                    # r,k,v,g,w trajectories
    out = 2 * b * s * d // tp
    total = 2 * b * s * spec.h // sp + proj + out + state
    if recompute == RecomputePolicy.SELECTIVE:
        total -= out  # recompute the scan output from saved projections
    return total


# ---------------------------------------------------------------------------
# Per-layer / per-device composition
# ---------------------------------------------------------------------------

def layer_activation_bytes(spec: ModelSpec, cfg: ParallelConfig,
                           layer_idx: int) -> ActivationBreakdown:
    b, s = cfg.micro_batch, cfg.seq_len
    kw = dict(tp=cfg.tp, sp=cfg.sp_degree, cp=cfg.cp, recompute=cfg.recompute)
    # attn_impl only reshapes the attention block's s² accounting
    akw = dict(kw, attn_impl=cfg.attn_impl)
    attn = 0
    if spec.attention == AttentionKind.MLA:
        attn = mla_activation_bytes(spec, b, s, **akw)
    elif spec.attention != AttentionKind.NONE:
        attn = gqa_activation_bytes(spec, b, s, **akw)
    ssm = ssm_activation_bytes(spec, b, s, **kw)
    if spec.is_moe and layer_idx in spec.moe_layer_indices():
        mlp = moe_activation_bytes(spec, b, s, sp=cfg.sp_degree, cp=cfg.cp,
                                   ep=cfg.ep, recompute=cfg.recompute)
    else:
        mlp = dense_mlp_activation_bytes(spec, b, s, **kw)
    return ActivationBreakdown(attn=attn, mlp=mlp, ssm=ssm,
                               per_layer=attn + mlp + ssm)


def one_f1b_in_flight(pp: int, stage: int, n_micro: Optional[int] = None) -> int:
    """In-flight (activation-resident) microbatches of PP ``stage`` under the
    1F1B schedule: stage s holds pp - s warmup forwards before its first
    backward frees one, capped by the number of microbatches.  Stage 0 is the
    worst case (pp in flight), the last stage holds exactly 1 — the
    stage-dependent multiplier the paper's §6 tables assume.

    Kept as the canonical special case; ``schedule_in_flight`` generalizes it
    across schedules."""
    return schedule_in_flight(pp, stage, n_micro, schedule="1f1b")


def schedule_in_flight(pp: int, rank: int, n_micro: Optional[int] = None, *,
                       schedule: str = "1f1b", n_chunks: int = 1) -> int:
    """Peak in-flight (activation-resident) microbatch×chunk units on PP
    ``rank`` under ``schedule`` — the closed forms the tick simulator
    (``core.schedules``) is property-tested against:

    * ``1f1b``:        min(M, pp - rank)
    * ``interleaved``: min(M·v, (v-1)·pp + 2·(pp - rank - 1) + 1)
      (each unit is one of the rank's v *chunks*, ~1/v of its layers)
    * ``dualpipe``:    min(⌈M/2⌉, pp - rank) + min(⌊M/2⌋, rank + 1)
      (≈ pp + 1 on every rank — DualPipe's near-flat profile)
    * ``zb1p``:        min(M, pp - rank) — same as 1f1b: the full-layer
      activation stash still retires at B (which runs the whole vjp); the
      deferred W ops instead park each pending microbatch's fp32
      pending-dW in the executor's stash ring until the W flush
      (``core.schedules.zb_pending_peak``), priced as grad memory by
      ``estimate_memory(schedule="zb1p")``

    ``n_micro=None`` gives the M→∞ steady-state value.
    """
    from .schedules import norm_chunks  # shared validation
    if not 0 <= rank < pp:
        raise ValueError(f"rank {rank} outside [0, {pp})")
    v = norm_chunks(schedule, n_chunks)
    if schedule in ("1f1b", "zb1p"):
        resident = pp - rank
        return min(n_micro, resident) if n_micro is not None else resident
    if schedule == "interleaved":
        resident = (v - 1) * pp + 2 * (pp - rank - 1) + 1
        return min(n_micro * v, resident) if n_micro is not None else resident
    # dualpipe
    ma = (n_micro + 1) // 2 if n_micro is not None else pp
    mb = n_micro // 2 if n_micro is not None else pp
    return min(ma, pp - rank) + min(mb, rank + 1)


def layers_activation_bytes(spec: ModelSpec, cfg: ParallelConfig,
                             layers) -> int:
    """Activation bytes of one microbatch across ``layers``, applying the
    recompute policy to the first ``recompute_fraction`` of them (paper §5's
    'how many layers to recompute')."""
    frac = cfg.recompute_fraction if cfg.recompute != RecomputePolicy.NONE \
        else 0.0
    n_rc = int(round(frac * len(layers)))
    no_rc = dataclasses.replace(cfg, recompute=RecomputePolicy.NONE)
    total = 0
    for i, l in enumerate(layers):
        c = cfg if i < n_rc else no_rc
        total += layer_activation_bytes(spec, c, l).per_layer
    return total


def stage_activation_bytes(spec: ModelSpec, cfg: ParallelConfig,
                           stage: int = None, in_flight: int = None) -> int:
    """Activation bytes held per device for one PP stage.

    ``in_flight`` microbatches are resident under 1F1B (stage_id-dependent,
    worst case = pp); default 1 reproduces the paper's single-microbatch
    Table 10 view.
    """
    from .params import table4_stages  # local import to avoid cycle
    stages = table4_stages(spec, cfg.pp)
    if stage is None:
        interior = [r for r in stages if 0 not in r.layers
                    and (spec.n_layers - 1) not in r.layers]
        row = max(interior or stages, key=lambda r: r.params)
    else:
        row = stages[stage]
    return layers_activation_bytes(spec, cfg, row.layers) * (in_flight or 1)


def rank_chunk_layers(spec: ModelSpec, pp: int, *, schedule: str = "1f1b",
                      n_chunks: int = 1):
    """Per-rank tuple of layer-id tuples, one per local chunk: the model is
    split into ``n_model_chunks`` contiguous pieces with the same Table-4
    front-loaded rule as plain PP (``params.pp_stage_layers``), then placed
    by ``core.schedules.schedule_placement``.  Under dualpipe every model
    chunk appears on two ranks (the schedule's 2× parameter cost)."""
    from .params import pp_stage_layers
    from .schedules import n_model_chunks, schedule_placement
    if schedule == "dualpipe" and pp < 2:
        raise ValueError("dualpipe needs pp >= 2 (pp=1 would duplicate the "
                         "whole model onto one rank)")
    g = n_model_chunks(schedule, pp, n_chunks)
    if g > spec.n_layers:
        raise ValueError(f"{g} model chunks need n_layers >= {g} "
                         f"(got {spec.n_layers})")
    pieces = pp_stage_layers(spec.n_layers, g)
    placement = schedule_placement(schedule, pp, n_chunks)
    return tuple(tuple(tuple(pieces[cid]) for cid in row)
                 for row in placement)


def schedule_activation_bytes(spec: ModelSpec, cfg: ParallelConfig,
                              rank: int, *, schedule: str = "1f1b",
                              n_chunks: int = 1,
                              n_micro: Optional[int] = None) -> int:
    """Schedule-aware peak activation residency (bytes) on PP ``rank``.

    Time-resolved: the tick simulator gives each chunk's in-flight count
    k_c(t); the reported peak is max_t Σ_c k_c(t)·bytes(chunk c), which is
    ≤ the sum of per-chunk peaks (chunks of a rank do not all peak at the
    same tick under interleaving).  For 1f1b this reduces exactly to
    ``stage_activation_bytes(stage=rank, in_flight=min(M, pp-rank))``.

    ``n_micro=None`` uses M = 2·pp (rounded up to a pp multiple), enough to
    reach every schedule's steady-state plateau.
    """
    from .schedules import make_schedule
    pp = cfg.pp
    if n_micro is None:
        n_micro = 2 * pp
    chunks = rank_chunk_layers(spec, pp, schedule=schedule,
                               n_chunks=n_chunks)[rank]
    weights = [layers_activation_bytes(spec, cfg, ls) for ls in chunks]
    if pp == 1:
        return sum(weights)          # no pipeline: one microbatch resident
    sched = make_schedule(schedule, pp, n_micro, n_chunks=len(chunks))
    peak, _ = sched.peak_profile(rank, weights)
    return int(peak)


def table10(spec: ModelSpec, cfg: ParallelConfig) -> Dict[str, Dict[str, int]]:
    """Paper Table 10: MLA / MoE / total per 4-layer stage, AC None vs Full."""
    out: Dict[str, Dict[str, int]] = {}
    for policy in (RecomputePolicy.NONE, RecomputePolicy.FULL):
        c = dataclasses.replace(cfg, recompute=policy)
        b, s = c.micro_batch, c.seq_len
        kw = dict(tp=c.tp, sp=c.sp_degree, cp=c.cp, recompute=policy,
                  attn_impl=c.attn_impl)
        mla = mla_activation_bytes(spec, b, s, **kw)
        moe = moe_activation_bytes(spec, b, s, sp=c.sp_degree, cp=c.cp,
                                   ep=c.ep, recompute=policy)
        out[policy.value] = {"MLA": 4 * mla, "MoE": 4 * moe,
                             "Total": 4 * (mla + moe)}
    return out
