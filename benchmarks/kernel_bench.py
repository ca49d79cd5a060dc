"""Kernel micro-benchmarks: pallas-vs-reference rows on the step-bench grid.

Every op the backend dispatcher (``repro.models.backend``) can route —
rmsnorm, flash attention, grouped-mlp gmm — is timed twice per shape:

* ``<op>.reference.<shape>`` — the jnp oracle (``repro.kernels.ref`` /
  the model-stack twin), i.e. what ``ModelOptions(backend="reference")``
  executes;
* ``<op>.pallas.<shape>``    — the Pallas kernel via ``repro.kernels.ops``
  (interpret mode off-TPU).

Shapes are aligned to ``benchmarks/step_bench.py``'s smoke cell
(qwen2-1.5b smoke spec, batch 8, seq 128, tp 2) so a kernel row's shape is
exactly what one executor shard feeds the kernel in the matching
BENCH_step.json cell — flash sees n_h/tp heads, gmm the (E, C, h) local
dispatch buffer.  ``--smoke`` keeps only those aligned shapes.

Wall-clock on CPU is NOT the TPU story: interpret-mode pallas lowers to
pure-jax emulation and is *expected* to be slower than the XLA-fused
reference there.  The ``--check`` gate is therefore host-aware:

* on TPU, pallas rmsnorm/flash must beat (or tie within ``--band``) the
  reference rows;
* off-TPU, the gate asserts row presence/finiteness, newest-wins dedupe,
  and the *analytic* direction instead — the flash row's derived resident
  act bytes must undercut the naive row's 5·b·n_h·s² (the claim the
  memory model prices; wall clock is not gated).

Rows land in BENCH_kernels.json, deduped newest-wins on ``name`` like
BENCH_step.json.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.train.timing import merge_rows, time_callable

ARTIFACT = os.path.join(os.path.dirname(__file__), "artifacts",
                        "BENCH_kernels.json")

# The step-bench smoke cell (benchmarks/step_bench.py: ARCH/BATCH/SEQ and
# the pp2·dp2·tp2 grid rows) seen from ONE executor shard:
#   qwen2-1.5b smoke → h=256, n_h=4, d_head=64; tp=2 → 2 heads/shard;
#   batch 8 over dp2 × n_micro4 → micro_batch 1; seq 128.
STEP_B, STEP_S, STEP_H = 1, 128, 256
STEP_NH_SHARD, STEP_D = 2, 64
# qwen2-moe-a2.7b smoke expert geometry: E=4 experts, h=256, d_ff=128;
# capacity C = S·n_active/E at capacity_factor 1 → 64 rows/expert.
STEP_E, STEP_C, STEP_DFF = 4, 64, 128


def _time(fn, *args, n=5) -> float:
    """Median-of-``n`` µs via the shared harness timer (warmup outside the
    timed windows, block inside each)."""
    return time_callable(fn, *args, iters=n, warmup=1).median_us


def _row(name: str, us: float, derived: str) -> Dict[str, Any]:
    return {"name": name, "us_per_call": us, "derived": derived,
            "timer": "median_of_5_blocked",
            "host": jax.default_backend()}


def bench_rmsnorm(smoke: bool) -> List[Dict[str, Any]]:
    shapes = [(STEP_B * STEP_S, STEP_H)]
    if not smoke:
        shapes.append((4096, 1024))
    rows = []
    for (r, h) in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (r, h), jnp.float32)
        s = jnp.ones((h,), jnp.float32)
        vmem_kib = (min(256, r) * h * 4 * 2) / 1024
        derived = f"tile_vmem={vmem_kib:.0f}KiB ai=O(1)"
        rows.append(_row(f"rmsnorm.reference.{r}x{h}",
                         _time(lambda: ref.rmsnorm_ref(x, s)), derived))
        rows.append(_row(f"rmsnorm.pallas.{r}x{h}",
                         _time(lambda: ops.rmsnorm(x, s)), derived))
    return rows


def bench_flash(smoke: bool) -> List[Dict[str, Any]]:
    shapes = [(STEP_B, STEP_S, STEP_NH_SHARD, STEP_D)]
    if not smoke:
        shapes.append((1, 1024, 4, 128))
    rows = []
    for (b, s, nh, d) in shapes:
        scale = d ** -0.5
        q = jax.random.normal(jax.random.PRNGKey(1), (b, s, nh, d),
                              jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(2), (b, s, nh, d),
                              jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(3), (b, s, nh, d),
                              jnp.float32)
        shape = f"b{b}s{s}h{nh}d{d}"
        naive_bytes = 5 * b * nh * s * s
        rows.append(_row(
            f"attn.reference.{shape}",
            _time(lambda: ref.flash_attention_ref(q, k, v, scale=scale)),
            f"act_bytes={naive_bytes}"))
        bq = min(128, s)
        tile = (bq * d * 4 * 4) / 1024
        # flash keeps only the (b, nh, s) row stats + output resident
        flash_bytes = 2 * b * nh * s * (d + 2)
        rows.append(_row(
            f"attn.pallas.{shape}",
            _time(lambda: ops.flash_attention(q, k, v, scale=scale,
                                              block_q=bq, block_k=bq)[0]),
            f"act_bytes={flash_bytes} tile_vmem={tile:.0f}KiB"))
        from repro.models.attention import chunked_attention
        rows.append(_row(
            f"attn.chunked.{shape}",
            _time(lambda: chunked_attention(q, k, v, scale, block=bq)),
            f"act_bytes={naive_bytes}"))   # scan residuals stay O(s²) under AD
    return rows


def bench_gmm(smoke: bool) -> List[Dict[str, Any]]:
    shapes = [(STEP_E, STEP_C, STEP_H, STEP_DFF)]
    if not smoke:
        shapes.append((8, 128, 256, 512))
    rows = []
    for (E, C, K, N) in shapes:
        x = jax.random.normal(jax.random.PRNGKey(4), (E * C, K), jnp.float32)
        rhs = jax.random.normal(jax.random.PRNGKey(5), (E, K, N), jnp.float32)
        bm = 128 if C % 128 == 0 else C
        emap = jnp.repeat(jnp.arange(E, dtype=jnp.int32), C // bm)
        shape = f"E{E}C{C}k{K}n{N}"
        mxu = 2 * bm * K * N
        moved = (bm * K + K * N + bm * N) * 4
        derived = f"tile_ai={mxu / moved:.0f}flops/B"
        rows.append(_row(
            f"gmm.reference.{shape}",
            _time(lambda: jnp.einsum("eck,ekn->ecn",
                                     x.reshape(E, C, K), rhs)), derived))
        rows.append(_row(
            f"gmm.pallas.{shape}",
            _time(lambda: ops.gmm(x, rhs, emap, block_m=bm,
                                  block_n=128 if N % 128 == 0 else N)),
            derived))
    return rows


def _act_bytes(derived: str) -> float:
    for tok in derived.split():
        if tok.startswith("act_bytes="):
            return float(tok.split("=", 1)[1])
    return math.nan


def check_rows(rows: List[Dict[str, Any]], *, band: float = 0.25) -> List[str]:
    """Host-aware CI gate over the artifact rows (see module docstring).
    Returns violation messages (empty == pass)."""
    bad: List[str] = []
    by_name = {r["name"]: r for r in rows}
    if len(by_name) != len(rows):
        from collections import Counter
        dup = [n for n, c in Counter(r["name"] for r in rows).items() if c > 1]
        bad.append(f"duplicate rows after dedupe: {dup}")
    for r in rows:
        us = r.get("us_per_call")
        if us is None or not math.isfinite(us) or us <= 0:
            bad.append(f"{r.get('name')}: non-finite us_per_call {us}")
    pallas = [n for n in by_name if ".pallas." in n]
    for op in ("rmsnorm", "attn", "gmm"):
        if not any(n.startswith(op + ".pallas.") for n in pallas):
            bad.append(f"no {op}.pallas.* row in the artifact")
        if not any(n.startswith(op + ".reference.") for n in by_name):
            bad.append(f"no {op}.reference.* row in the artifact")
    on_tpu = any(r.get("host") == "tpu" for r in rows)
    if on_tpu:
        for n in pallas:
            twin = n.replace(".pallas.", ".reference.")
            if twin not in by_name or n.startswith("gmm."):
                continue           # gmm's einsum twin fuses differently; no gate
            pu, ru = by_name[n]["us_per_call"], by_name[twin]["us_per_call"]
            if pu > ru * (1 + band):
                bad.append(f"{n}: {pu:.1f}us exceeds {twin} {ru:.1f}us "
                           f"beyond the {band:.0%} band on TPU")
    else:
        # interpret-mode host: wall clock is meaningless for the kernels;
        # gate the analytic direction the memory model prices instead
        for n in by_name:
            if not n.startswith("attn.pallas."):
                continue
            twin = n.replace(".pallas.", ".reference.")
            if twin not in by_name:
                continue
            fb = _act_bytes(by_name[n]["derived"])
            nb = _act_bytes(by_name[twin]["derived"])
            if not (fb < nb):
                bad.append(f"{n}: derived act_bytes {fb} not below "
                           f"{twin}'s {nb} (flash must drop the s² term)")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="step-grid-aligned shapes only (CI tier)")
    ap.add_argument("--out", default=ARTIFACT)
    ap.add_argument("--check", action="store_true",
                    help="host-aware gate over the artifact (no new "
                         "measurements): timing ordering on TPU, "
                         "presence/finiteness + analytic act-bytes "
                         "direction off-TPU")
    ap.add_argument("--band", type=float, default=0.25,
                    help="relative tie band for the TPU timing gate")
    args = ap.parse_args(argv)

    if args.check:
        if not os.path.exists(args.out):
            print(f"no artifact at {args.out}; run the bench first",
                  file=sys.stderr)
            return 2
        with open(args.out) as f:
            rows = json.load(f)
        bad = check_rows(rows, band=args.band)
        for msg in bad:
            print(f"KERNEL BENCH VIOLATION: {msg}", file=sys.stderr)
        print(f"kernel bench check: {len(rows)} rows, {len(bad)} violations")
        return 1 if bad else 0

    rows: List[Dict[str, Any]] = []
    for fn in (bench_rmsnorm, bench_flash, bench_gmm):
        for row in fn(args.smoke):
            rows.append(row)
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
    existing = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(merge_rows(existing, rows, ("name",)), f, indent=1)
        f.write("\n")
    print(f"wrote {len(rows)} rows -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
