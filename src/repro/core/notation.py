"""Model-architecture notation (paper Table 1/2, generalized to 6 families).

The paper analyses DeepSeek-v3; the assigned-architecture pool additionally
spans dense (GQA/MQA), MoE (standard SwiGLU experts), SSM (RWKV6), hybrid
(Hymba: parallel attention+SSM heads), enc-dec audio (Whisper) and VLM
(Qwen2-VL decoder).  ``ModelSpec`` is the single structural description that
both the analytical memory model (``repro.core``) and the runtime model
builder (``repro.models``) consume, so the two can never drift apart.
"""

from __future__ import annotations

import dataclasses
import enum
import math
from typing import Optional, Tuple


class AttentionKind(enum.Enum):
    """Which attention mechanism a layer uses."""

    MHA = "mha"            # n_kv == n_h
    GQA = "gqa"            # 1 < n_kv < n_h
    MQA = "mqa"            # n_kv == 1
    MLA = "mla"            # DeepSeek multi-head latent attention
    NONE = "none"          # attention-free (pure SSM)


class MlpKind(enum.Enum):
    SWIGLU = "swiglu"      # gate/up/down, 3 matrices (DeepSeek, Qwen, OLMoE)
    GEGLU = "geglu"        # gate/up/down with GeLU (Gemma)
    GELU = "gelu"          # fc1/fc2, 2 matrices (Whisper)


class FamilyKind(enum.Enum):
    DENSE = "dense"
    MOE = "moe"
    SSM = "ssm"
    HYBRID = "hybrid"      # parallel attention + SSM heads (Hymba)
    AUDIO = "audio"        # encoder-decoder (Whisper)
    VLM = "vlm"            # dense decoder consuming patch embeddings


@dataclasses.dataclass(frozen=True)
class MLASpec:
    """Multi-head latent attention dimensions (paper Table 1)."""

    # query compression dim (q_lora_rank); None: no query LoRA, one W^Q
    # projects the hidden state to every head's (d_h + d_hr) query
    # (DeepSeek-V2-Lite)
    d_cq: Optional[int] = 1536
    d_c: int = 512         # key-value compression dim (kv_lora_rank)
    d_h: int = 128         # qk_nope_head_dim
    d_hr: int = 64         # qk_rope_head_dim
    d_v: int = 128         # v_head_dim


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """Mixture-of-experts dimensions (paper Table 1)."""

    n_routed: int          # N   — routed experts per MoE layer
    n_active: int          # N_r — routed experts per token (top-k)
    n_shared: int = 0      # N_s — shared experts (always-on)
    d_ff_expert: int = 0   # h_E — expert MLP hidden dim
    # layers [0, first_k_dense) use a dense FFN instead of MoE (DeepSeek: 3).
    first_k_dense: int = 0
    router_bias: bool = False
    # experts [0, n_held) of the n_routed live in this layer (the chip's
    # share under expert parallelism); the router still scores n_routed.
    # None: every expert (``held``)
    n_held: Optional[int] = None
    norm_topk: bool = True     # renormalise the top-k gates to sum to one
    aux_coef: float = 0.01     # weight of the load-balance loss

    def __post_init__(self):
        if self.n_held is not None and not 0 < self.n_held <= self.n_routed:
            raise ValueError(f"n_held={self.n_held} must be in "
                             f"[1, n_routed={self.n_routed}]")

    @property
    def held(self) -> int:
        """The routed experts this layer holds."""
        return self.n_routed if self.n_held is None else self.n_held


@dataclasses.dataclass(frozen=True)
class YaRNSpec:
    """YaRN rope scaling (arXiv:2309.00071, DeepSeek-V2's ``rope_scaling``).

    The softmax scale takes ``mscale(factor, mscale_all_dim)²``; cos and
    sin would take the ratio ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, which is 1 where the two coefficients are equal, as
    in DeepSeek-V2 and V2-Lite, the only setting built."""

    factor: float                  # context extension factor
    original_max_position: int     # the length the rope was trained at
    beta_fast: float = 32.0        # rotations above which dims extrapolate
    beta_slow: float = 1.0         # rotations below which dims interpolate
    mscale: float = 1.0            # rope-amplitude mscale coefficient
    mscale_all_dim: float = 1.0    # softmax-scale mscale coefficient

    def __post_init__(self):
        if self.mscale != self.mscale_all_dim:
            raise ValueError(
                f"YaRN with mscale {self.mscale} != mscale_all_dim "
                f"{self.mscale_all_dim} scales cos and sin, which is not "
                "built")


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """State-space / RWKV recurrent path dimensions."""

    state_dim: int         # per-head recurrent state size (rwkv head dim / mamba d_state)
    n_ssm_heads: int       # number of recurrent heads
    conv_kernel: int = 0   # depthwise conv width (mamba-style); 0 = none
    ssm_expand: int = 1    # channel expansion factor of the recurrent block


@dataclasses.dataclass(frozen=True)
class EncoderSpec:
    """Encoder tower of an enc-dec model (Whisper). Frontend is stubbed."""

    n_layers: int
    n_ctx: int             # encoder sequence length (whisper: 1500)
    frontend: str = "stub" # mel+conv stub: input_specs supplies embeddings


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Complete structural description of one architecture.

    Field names follow the paper's notation where one exists:
    ``h`` hidden dim, ``h_ff`` dense-MLP hidden (h_F), ``n_h`` heads,
    ``d_h`` head dim, ``n_layers`` (l), ``vocab`` (v).
    """

    name: str
    family: FamilyKind
    n_layers: int
    h: int
    n_h: int
    n_kv: int
    d_head: int
    h_ff: int
    vocab: int
    attention: AttentionKind = AttentionKind.GQA
    mlp: MlpKind = MlpKind.SWIGLU
    mla: Optional[MLASpec] = None
    moe: Optional[MoESpec] = None
    ssm: Optional[SSMSpec] = None
    encoder: Optional[EncoderSpec] = None
    tie_embeddings: bool = False
    qkv_bias: bool = False
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    yarn: Optional[YaRNSpec] = None
    # Sliding-window decode variant (enables long_500k for full-attention archs).
    sliding_window: Optional[int] = None
    max_seq_len: int = 32768
    notes: str = ""

    # ------------------------------------------------------------------
    # Derived quantities
    # ------------------------------------------------------------------

    @property
    def is_moe(self) -> bool:
        return self.moe is not None

    @property
    def attn_free(self) -> bool:
        return self.attention == AttentionKind.NONE

    @property
    def aux_coef(self) -> float:
        """Weight of the MoE load-balance loss in the training loss (a
        dense model's aux is zero; it keeps the 0.01 default)."""
        return self.moe.aux_coef if self.is_moe else MoESpec.aux_coef

    def moe_layer_indices(self) -> Tuple[int, ...]:
        if not self.is_moe:
            return ()
        return tuple(range(self.moe.first_k_dense, self.n_layers))

    def n_moe_layers(self) -> int:
        return len(self.moe_layer_indices())

    def n_dense_layers(self) -> int:
        return self.n_layers - self.n_moe_layers()

    # -- parameter counts (exact; used by core.params and asserted in tests)

    def attn_params_per_layer(self, include_qk_norm: bool = True) -> int:
        """Parameters of one attention block (projections (+biases) only)."""
        if self.attention == AttentionKind.MLA:
            m = self.mla
            tp_split = (
                m.d_h * self.n_h * m.d_c         # W^UK
                + m.d_v * self.n_h * m.d_c       # W^UV
                + self.h * m.d_v * self.n_h      # W^O
            )
            replicated = (
                m.d_c * self.h                   # W^DKV
                + m.d_hr * self.h                # W^KR
            )
            if m.d_cq is None:                   # W^Q
                tp_split += self.h * self.n_h * (m.d_h + m.d_hr)
            else:
                tp_split += m.d_h * self.n_h * m.d_cq         # W^UQ
                replicated += (m.d_cq * self.h                # W^DQ
                               + m.d_hr * self.n_h * m.d_cq)  # W^QR
            total = tp_split + replicated
            if include_qk_norm:
                total += self.mla_norm_params()  # q/kv RMSNorm (paper Table 3)
            return total
        if self.attention == AttentionKind.NONE:
            return 0
        q = self.h * self.n_h * self.d_head
        kv = 2 * self.h * self.n_kv * self.d_head
        o = self.n_h * self.d_head * self.h
        bias = 0
        if self.qkv_bias:
            bias = self.n_h * self.d_head + 2 * self.n_kv * self.d_head
        return q + kv + o + bias

    def mlp_params(self, d_ff: int) -> int:
        if self.mlp in (MlpKind.SWIGLU, MlpKind.GEGLU):
            return 3 * self.h * d_ff
        return 2 * self.h * d_ff          # GELU: fc1 + fc2

    def dense_mlp_params_per_layer(self) -> int:
        return self.mlp_params(self.h_ff)

    def moe_params_per_layer(self) -> int:
        """Router (gate) + all experts of one MoE layer."""
        if not self.is_moe:
            return 0
        e = self.moe
        router = e.n_routed * self.h + (e.n_routed if e.router_bias else 0)
        experts = 3 * self.h * e.d_ff_expert * (e.held + e.n_shared)
        return router + experts

    def moe_active_params_per_layer(self) -> int:
        if not self.is_moe:
            return 0
        e = self.moe
        router = e.n_routed * self.h
        experts = 3 * self.h * e.d_ff_expert * (e.n_active + e.n_shared)
        return router + experts

    def ssm_params_per_layer(self) -> int:
        """RWKV6-style time-mix block (approximate but consistent w/ runtime)."""
        if self.ssm is None:
            return 0
        s = self.ssm
        d = self.h * s.ssm_expand
        # r/k/v/g/o projections + data-dependent decay low-rank (w1,w2) + u
        proj = 5 * self.h * d
        decay = self.h * 64 + 64 * d + d       # lora-style decay + per-channel u
        tokenshift = 6 * self.h                # per-channel interpolation mus
        conv = s.conv_kernel * d if s.conv_kernel else 0
        return proj + decay + tokenshift + conv

    def mla_norm_params(self) -> int:
        """MLA's latent RMSNorms: the query's (d_cq, where the query is
        compressed) and the key-value latent's (d_c)."""
        return (self.mla.d_cq or 0) + self.mla.d_c

    def norm_params_per_layer(self) -> int:
        n = 2 * self.h
        if self.attention == AttentionKind.MLA:
            n += self.mla_norm_params()   # counted in LN row by the paper
        return n

    def embedding_params(self) -> int:
        return self.vocab * self.h

    def layer_params(self, layer_idx: int) -> int:
        """Total parameters of transformer layer ``layer_idx`` (no emb/head).

        Matches paper Table 3 semantics: MLA row includes qk-norms, LN row
        counts them again (paper double-count reproduced via report.py, not
        here — here each param is counted once).
        """
        p = self.attn_params_per_layer(include_qk_norm=False)
        p += self.norm_params_per_layer()
        if self.ssm is not None:
            p += self.ssm_params_per_layer()
            if self.family == FamilyKind.HYBRID:
                p += self.h  # extra norm merging parallel heads
        if self.is_moe and layer_idx in self.moe_layer_indices():
            p += self.moe_params_per_layer()
        elif self.h_ff:
            p += self.dense_mlp_params_per_layer()
        return p

    def total_params(self) -> int:
        body = sum(self.layer_params(i) for i in range(self.n_layers))
        emb = self.embedding_params()
        head = 0 if self.tie_embeddings else self.embedding_params()
        final_norm = self.h
        enc = 0
        if self.encoder is not None:
            # encoder layers: MHA + GELU MLP + norms (+ cross-attn lives in decoder)
            per = (4 * self.h * self.h) + self.mlp_params(self.h_ff) + 2 * self.h
            enc = self.encoder.n_layers * per + self.h
            # decoder cross-attention adds 4*h*h + its layernorm per layer
            body += self.n_layers * (4 * self.h * self.h + self.h)
        return body + emb + head + final_norm + enc

    def active_params(self) -> int:
        """Activated parameters per token (= total for non-MoE)."""
        if not self.is_moe:
            return self.total_params()
        per_layer_delta = self.moe_params_per_layer() - self.moe_active_params_per_layer()
        return self.total_params() - per_layer_delta * self.n_moe_layers()


def tp_violations(spec: "ModelSpec", tp: int, *, sp: int = 1,
                  seq_len: Optional[int] = None, ep: int = 1,
                  attn_impl: str = "naive"):
    """Dims a TP degree fails to divide exactly, as human-readable strings
    (empty list = cleanly divisible).  Shared by the analytic guard
    (``core.activations``), the planner's runnable marking and the
    executor's hard checks (``parallel.tp.check_tp_supported`` /
    ``check_sp_supported`` / ``check_ep_supported``).

    ``sp``/``seq_len`` extend the check to sequence parallelism: SP shards
    the token dim, so ``seq_len % sp`` must be 0 (the executor's boundary
    all-gather/reduce-scatter pair has no replicate-fallback; the analytic
    model falls back to SP-replicated accounting with a RuntimeWarning —
    ``core.activations._seq_shard_or_warn``).

    ``ep`` extends it to expert parallelism: the expert-dim weight shard
    requires ``n_routed % ep == 0`` (the analytic fallback is
    EP-replicated accounting — ``core.activations._shard_or_warn``).

    ``attn_impl`` in ``("flash", "pallas")`` extends it to the flash
    kernel's tiling: block_q = min(128, s) must divide the sequence the
    kernel sees (the FULL sequence — SP gathers before attention) — the
    kernel pads internally, but the analytic model does not price pad
    blocks, so the executor refuses padded-flash configs."""
    bad = []
    if sp > 1 and seq_len is not None and seq_len % sp:
        bad.append(f"s={seq_len} (sp={sp})")
    if attn_impl in ("flash", "pallas") and seq_len is not None \
            and spec.attention != AttentionKind.NONE:
        bq = min(128, seq_len)
        if seq_len % bq:
            bad.append(f"s={seq_len} (flash block_q={bq})")
    if ep > 1 and spec.is_moe and spec.moe.n_routed % ep:
        bad.append(f"n_routed={spec.moe.n_routed} (ep={ep})")
    if tp <= 1:
        return bad
    if spec.attention != AttentionKind.NONE and spec.n_h % tp:
        bad.append(f"n_h={spec.n_h}")
    if spec.attention not in (AttentionKind.NONE, AttentionKind.MLA) \
            and spec.n_kv % tp:
        bad.append(f"n_kv={spec.n_kv}")
    if spec.h_ff and spec.h_ff % tp:
        bad.append(f"h_ff={spec.h_ff}")
    if spec.is_moe and spec.moe.d_ff_expert % tp:
        bad.append(f"d_ff_expert={spec.moe.d_ff_expert}")
    if spec.vocab % tp:
        bad.append(f"vocab={spec.vocab}")
    return bad


def human_bytes(n: float) -> str:
    """GiB-based formatting matching the paper's 'GB' (actually GiB) usage."""
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if abs(n) >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n:.0f} B"


def human_count(n: float) -> str:
    if abs(n) >= 1e9:
        return f"{n / 1e9:.2f}B"
    if abs(n) >= 1e6:
        return f"{n / 1e6:.2f}M"
    return f"{n:,.0f}"
