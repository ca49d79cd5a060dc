"""The analytical model and the runtime must agree to the PARAMETER: for
every architecture, ModelSpec.total_params() == the abstract-init leaf sum.
This is the contract that makes the memory model trustworthy (DESIGN.md §2).
"""

import math

import jax
import pytest

from repro.configs import ARCHS, get_spec
from repro.models import build_model


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("smoke", [True, False])
def test_runtime_matches_analytic_param_count(arch, smoke):
    spec = get_spec(arch, smoke=smoke)
    ap = build_model(spec).abstract_params()
    runtime = sum(math.prod(l.shape) for l in jax.tree.leaves(ap))
    assert runtime == spec.total_params(), (
        f"{arch} smoke={smoke}: runtime {runtime:,} != "
        f"analytic {spec.total_params():,} "
        f"(diff {runtime - spec.total_params():,})")


def test_deepseek_paper_vs_dedup_count():
    """Paper's Table-3 total includes the qk-norm double count (61×2048) and
    omits the final norm (7168); the de-duplicated truth differs by exactly
    that."""
    from repro.core.params import total_params_paper
    spec = get_spec("deepseek-v3")
    paper = total_params_paper(spec)
    exact = spec.total_params()
    assert paper - exact == 61 * 2048 - 7168


def test_active_params_moe():
    spec = get_spec("deepseek-v3")
    active = spec.active_params()
    # DeepSeek-v3: ~37B activated of 671B total
    assert 35e9 < active < 40e9, active / 1e9
    olmoe = get_spec("olmoe-1b-7b")
    # OLMoE: ~1.3B active of ~6.9B total
    assert 0.9e9 < olmoe.active_params() < 1.7e9


def test_deepseek_v2_lite_published_counts():
    """DeepSeek-V2-Lite: 15.7 B total, 2.4 B active (DeepSeek-V2 report,
    appendix B), with MLA priced without a query LoRA: W^Q (2048 x 16 x
    192) in place of W^DQ, W^UQ, W^QR and the query norm.  The report's
    active count leaves out the input embedding, a lookup: 2.45 B, which
    it gives as 2.4."""
    spec = get_spec("deepseek-v2-lite")
    assert spec.attn_params_per_layer(include_qk_norm=False) == (
        2048 * 16 * 192 + 2048 * 512 + 2048 * 64 + 512 * 16 * 128 * 2
        + 16 * 128 * 2048)
    assert spec.norm_params_per_layer() == 2 * 2048 + 512
    total = spec.total_params()
    assert round(total / 1e8) / 10 == 15.7, total
    active = spec.active_params() - spec.embedding_params()
    assert math.floor(active / 1e8) / 10 == 2.4, active
