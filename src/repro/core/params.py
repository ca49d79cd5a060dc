"""Parameter counting (paper §2-§3, Tables 3, 4, 6).

Two counting modes exist:

* ``paper mode`` — reproduces the paper's Table 3 row values *exactly*,
  including its quirk of counting MLA's q/kv RMSNorm weights both inside the
  MLA row (187,107,328) and inside the LN row (16,384).  Used by report.py
  and the table benchmarks.
* ``exact mode`` — ``ModelSpec.layer_params`` counts every parameter once;
  used by the runtime validation (matches ``jax.tree`` leaf counts of the
  real model to the parameter).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

from .notation import AttentionKind, ModelSpec
from .parallel_config import ParallelConfig, ZeROStage


# ---------------------------------------------------------------------------
# Table 3 — layer-level counting (paper mode)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerRow:
    layers: str
    modules: Dict[str, int]     # module name -> parameter count
    per_layer: int              # total per single layer in this group
    n_layers: int

    @property
    def group_total(self) -> int:
        return self.per_layer * self.n_layers


def mla_params_paper(spec: ModelSpec) -> int:
    """MLA row of Table 3: projections + q/kv norms (paper includes them)."""
    return spec.attn_params_per_layer(include_qk_norm=True)


def ln_params_paper(spec: ModelSpec) -> int:
    """LN row of Table 3: 2*h + d_cq + d_c (double-counts the qk norms)."""
    n = 2 * spec.h
    if spec.attention == AttentionKind.MLA:
        n += spec.mla_norm_params()
    return n


def table3_rows(spec: ModelSpec) -> List[LayerRow]:
    """Layer-level rows in the paper's grouping for a DeepSeek-style model."""
    assert spec.is_moe and spec.attention == AttentionKind.MLA, \
        "table3 is defined for the paper's MLA+MoE family"
    mla = mla_params_paper(spec)
    ln = ln_params_paper(spec)
    dense_mlp = spec.dense_mlp_params_per_layer()
    gate = spec.moe.n_routed * spec.h
    experts = 3 * spec.h * spec.moe.d_ff_expert * (spec.moe.held + spec.moe.n_shared)
    emb = spec.embedding_params()
    k = spec.moe.first_k_dense
    l = spec.n_layers

    rows = [
        LayerRow("Layer 0",
                 {"Embedding": emb, "MLA": mla, "MLP": dense_mlp, "LN": ln},
                 emb + mla + dense_mlp + ln, 1),
        LayerRow(f"Layers 1 - {k - 1}",
                 {"MLA": mla, "MLP": dense_mlp, "LN": ln},
                 mla + dense_mlp + ln, k - 1),
        LayerRow(f"Layers {k} - {l - 2}",
                 {"MLA": mla, "Gate": gate, "MoE": experts, "LN": ln},
                 mla + gate + experts + ln, l - 1 - k),
        LayerRow(f"Layer {l - 1}",
                 {"MLA": mla, "Gate": gate, "MoE": experts, "LN": ln, "Head": emb},
                 mla + gate + experts + ln + emb, 1),
    ]
    return rows


def total_params_paper(spec: ModelSpec) -> int:
    return sum(r.group_total for r in table3_rows(spec))


# ---------------------------------------------------------------------------
# Table 4 — pipeline-parallel stage assignment
# ---------------------------------------------------------------------------

def pp_stage_layers(n_layers: int, pp: int) -> List[List[int]]:
    """Paper's PP16 split of 61 layers: 4,4,...,4,1 (embedding-heavy stage 0
    gets the first layers; the lone head layer is stage pp-1).  General rule:
    distribute ceil/floor evenly, front-loaded, with the remainder-1 final
    stage when n_layers % pp != 0, matching the paper's 15*4+1 split."""
    if pp == 1:
        return [list(range(n_layers))]
    base = n_layers // pp
    rem = n_layers % pp
    if rem:
        # front stages get base+? — paper: 61/16 -> 15 stages of 4, 1 stage of 1
        sizes = [base + 1] * rem + [base] * (pp - rem)
        # paper puts the small remainder at the END (stage 15 has 1 layer)
        if base * pp + rem == n_layers and sizes[-1] != 1 and n_layers == 61 and pp == 16:
            sizes = [4] * 15 + [1]
    else:
        sizes = [base] * pp
    # normalize: ensure sum matches
    total = sum(sizes)
    if total != n_layers:
        sizes[-1] += n_layers - total
    out, i = [], 0
    for s in sizes:
        out.append(list(range(i, i + s)))
        i += s
    return out


def layer_params_paper(spec: ModelSpec, layer_idx: int) -> int:
    """Per-layer total in paper mode (incl. emb on layer 0, head on last)."""
    mla = mla_params_paper(spec) if spec.attention == AttentionKind.MLA else \
        spec.attn_params_per_layer()
    ln = ln_params_paper(spec)
    p = mla + ln
    if spec.is_moe and layer_idx in spec.moe_layer_indices():
        p += spec.moe.n_routed * spec.h
        p += 3 * spec.h * spec.moe.d_ff_expert * (spec.moe.held + spec.moe.n_shared)
    else:
        p += spec.dense_mlp_params_per_layer()
    if layer_idx == 0:
        p += spec.embedding_params()
    if layer_idx == spec.n_layers - 1 and not spec.tie_embeddings:
        p += spec.embedding_params()
    return p


@dataclasses.dataclass(frozen=True)
class StageRow:
    stage: int
    layers: List[int]
    params: int


def table4_stages(spec: ModelSpec, pp: int) -> List[StageRow]:
    stages = pp_stage_layers(spec.n_layers, pp)
    return [StageRow(i, ls, sum(layer_params_paper(spec, l) for l in ls))
            for i, ls in enumerate(stages)]


def max_stage(spec: ModelSpec, pp: int) -> StageRow:
    return max(table4_stages(spec, pp), key=lambda r: r.params)


# ---------------------------------------------------------------------------
# Table 6 — static parameters per device under TP/EP/ETP (one PP stage)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceParams:
    """Per-device parameter counts of one PP stage, split by gradient-sync
    group (the ZeRO math needs non-expert vs expert separated, paper §4)."""

    norms: int              # replicated across TP
    attn_tp: int            # TP-partitioned attention params (per rank)
    attn_replicated: int    # TP-replicated attention params
    dense_mlp: int          # TP-partitioned dense-MLP params (per rank)
    router: int             # replicated router/gate
    experts: int            # per-EP-rank expert params (incl. shared, / ETP)
    ssm: int                # recurrent-path params (TP-partitioned)
    embed: int              # embedding/head share on this stage (TP-split, vocab dim)

    @property
    def non_expert(self) -> int:
        return (self.norms + self.attn_tp + self.attn_replicated
                + self.dense_mlp + self.ssm + self.embed)

    @property
    def expert(self) -> int:
        return self.router + self.experts

    @property
    def total(self) -> int:
        return self.non_expert + self.expert


def _shard(count: int, tp: int, dim: int) -> int:
    """Per-rank share of ``count`` params whose sharded dim has size ``dim``:
    divide by tp when divisible, else replicate (matching the runtime's
    divisibility fallback — validated against XLA, see EXPERIMENTS.md
    §Validation)."""
    return count // tp if dim % tp == 0 else count


def attn_tp_split(spec: ModelSpec, tp: int) -> Tuple[int, int]:
    """(tp_partitioned_per_rank, replicated) attention params for one layer.

    MLA follows Megatron: W^UQ/W^UK/W^UV/W^O split, W^DQ/W^DKV/W^QR/W^KR
    replicated (paper §3.2); without a query LoRA the one W^Q is split by
    heads like W^UQ.  GQA/MQA: q/k/v/o sharded on the head-columns
    dim when divisible (TPU runtime semantics — columns, not whole heads).
    """
    if spec.attention == AttentionKind.NONE:
        return 0, 0
    if spec.attention == AttentionKind.MLA:
        m = spec.mla
        split = (_shard(m.d_h * spec.n_h * m.d_c, tp, m.d_h * spec.n_h)
                 + _shard(m.d_v * spec.n_h * m.d_c, tp, m.d_v * spec.n_h)
                 + _shard(spec.h * m.d_v * spec.n_h, tp, m.d_v * spec.n_h))
        repl = m.d_c * spec.h + m.d_hr * spec.h
        if m.d_cq is None:
            q_cols = spec.n_h * (m.d_h + m.d_hr)
            split += _shard(spec.h * q_cols, tp, q_cols)
        else:
            split += _shard(m.d_h * spec.n_h * m.d_cq, tp, m.d_h * spec.n_h)
            repl += m.d_cq * spec.h + m.d_hr * spec.n_h * m.d_cq
        return split, repl
    qdim = spec.n_h * spec.d_head
    kvdim = spec.n_kv * spec.d_head
    split = (_shard(spec.h * qdim, tp, qdim)          # wq
             + _shard(qdim * spec.h, tp, qdim)        # wo
             + 2 * _shard(spec.h * kvdim, tp, kvdim))  # wk, wv
    if spec.qkv_bias:
        split += _shard(qdim, tp, qdim) + 2 * _shard(kvdim, tp, kvdim)
    return split, 0


def device_params(spec: ModelSpec, cfg: ParallelConfig,
                  stage: int = None,
                  layers: Sequence[int] = None) -> DeviceParams:
    """Static parameters per device for one PP stage (default: the largest
    all-MoE stage, as the paper's §3 case study uses stages 1-14).

    ``layers`` overrides the Table-4 stage row with an explicit layer-id
    list — the schedule-aware path uses it for ranks that hold several
    chunks (interleaved virtual stages; dualpipe's duplicated stages, where
    a layer id appearing twice is counted twice — the 2× parameter cost)."""
    if layers is None:
        stages = table4_stages(spec, cfg.pp)
        if stage is None:
            # paper picks a maximal interior stage (no embedding): stages 1-14
            interior = [r for r in stages if 0 not in r.layers
                        and (spec.n_layers - 1) not in r.layers]
            row = max(interior or stages, key=lambda r: r.params)
        else:
            row = stages[stage]
        layers = row.layers

    norms = attn_tp = attn_repl = dense = router = experts = ssm = embed = 0
    for l in layers:
        norms += spec.norm_params_per_layer()
        if spec.ssm is not None and spec.family.value == "hybrid":
            norms += spec.h                                   # merge_norm
        s, r = attn_tp_split(spec, cfg.tp)
        attn_tp += s
        attn_repl += r
        if spec.encoder is not None:
            # decoder cross-attention: 4 h×h matrices + its norm
            attn_tp += 4 * _shard(spec.h * spec.h, cfg.tp, spec.h)
            norms += spec.h
        if spec.ssm is not None:
            ss = spec.ssm
            d = spec.h * ss.ssm_expand
            proj = 5 * _shard(spec.h * d, cfg.tp, d)
            decay = spec.h * 64 + _shard(64 * d, cfg.tp, d) \
                + _shard(d, cfg.tp, d)
            rest = 6 * spec.h + (ss.conv_kernel * d if ss.conv_kernel else 0)
            ssm += proj + decay + rest
        if spec.is_moe and l in spec.moe_layer_indices():
            router += spec.moe.n_routed * spec.h
            n_local = spec.moe.held // cfg.ep
            per_expert = 3 * spec.h * spec.moe.d_ff_expert // cfg.etp
            # shared experts replicated across EP ranks (paper §3.3)
            experts += (n_local + spec.moe.n_shared) * per_expert
        elif spec.h_ff:
            dense += spec.dense_mlp_params_per_layer() // cfg.tp \
                if spec.h_ff % cfg.tp == 0 else spec.dense_mlp_params_per_layer()
        if l == 0:
            embed += _shard(spec.embedding_params(), cfg.tp, spec.vocab)
        if l == spec.n_layers - 1 and not spec.tie_embeddings:
            embed += _shard(spec.embedding_params(), cfg.tp, spec.vocab)
    # encoder tower (whisper): colocated with the (single-PP-stage) decoder
    if spec.encoder is not None and (0 in layers or cfg.pp == 1):
        per = (4 * _shard(spec.h * spec.h, cfg.tp, spec.h)
               + _shard(spec.mlp_params(spec.h_ff), cfg.tp, spec.h_ff)
               + 2 * spec.h)
        embed += spec.encoder.n_layers * per + spec.h
    return DeviceParams(norms=norms, attn_tp=attn_tp, attn_replicated=attn_repl,
                        dense_mlp=dense, router=router, experts=experts,
                        ssm=ssm, embed=embed)


def device_param_bytes(spec: ModelSpec, cfg: ParallelConfig) -> int:
    d = device_params(spec, cfg)
    per = d.total
    if cfg.zero == ZeROStage.OS_G_PARAMS:
        # ceil: shards are ceil(n/group)-sized, the last rank pads
        per = -(-d.non_expert // cfg.dp) + -(-d.expert // cfg.edp)
    return per * cfg.dtype.weights
