"""Step-time benchmark: measured wall clock per pipeline schedule.

Runs ``make_pipeline_train_step`` over a (schedule, pp, tp, sp, ep, zero)
grid on the CPU fake-device mesh, times the *warm* jitted step
(median-of-k, blocked — ``repro.train.timing``), derives tokens/s and
analytic-FLOPs MFU, and records the two analytic views next to every
measurement:

* ``ideal_bubble_fraction`` — ``core.steptime.bubble_stats``, the paper
  story: what the schedule's bubble costs on hardware that skips idle
  slots (zb1p < 1f1b; dualpipe lowest).
* ``predicted_s`` — ``core.steptime.predict_step_time``'s *overlapped*
  view: what the cond-gated overlap engine should measure — per tick the
  active compute (F=1, fused B=4, zb1p's split B=3 / W=0.25
  chunk-forward units) with ring traffic overlapped against it.  On a
  host whose fake devices share cores (``host_serializes_ranks``) the
  per-tick cost is the *sum* of the ranks' active compute rather than
  the slowest rank's — the ranks' programs run back-to-back, so only
  total-work differences and tick counts are measurable here.
  The steps run with ``recompute=FULL`` (the documented chunk-recompute
  configuration) so the fused backward really pays the replay the model
  prices; zb1p's no-remat B skips that replay by stashing the fp32
  pending-dW instead of recomputing activations, and its W ticks are
  near-free flushes — the remat asymmetry the split exploits.  The
  sequence length is chosen long enough that the replay is real compute
  (at tiny shapes forward replay hides in memory latency and remat ≈
  no-remat, which would erase the asymmetry being measured).

Every row also records ``ticks_total`` (tick count × pp rank-ticks),
``ticks_active`` (rank-ticks with gated work) and the per-kind
``ticks_f``/``ticks_b``/``ticks_w`` sums from the exec tables, so the
artifact shows how much of each timeline the cond gates skip.

``--check-direction`` asserts the measured ranking matches the executor
model's ranking for pairs whose predicted times differ by >10% — the
CI-gated perf trajectory: an executor regression that inverts a schedule
ordering fails loudly, while CPU noise inside the 10% band cannot flake.
``--check-convergence`` is the overlap gate: measured zb1p must not
exceed measured 1f1b by more than the tie band in any shared cell, and
every pp>1 row must actually skip work (``ticks_active < ticks_total``).

Rows land in ``benchmarks/artifacts/BENCH_step.json`` keyed on the full
config tuple, newest-wins (same dedupe policy as ``validate_memory``'s
per-config artifacts), so the committed file is a perf trajectory that
re-runs extend rather than clobber.

Usage::

    python benchmarks/step_bench.py                  # full grid, write JSON
    python benchmarks/step_bench.py --smoke          # pp2-only CI tier
    python benchmarks/step_bench.py --check-direction  # gate on existing rows
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

N_DEVICES = 8

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))


def _ensure_fake_devices() -> None:
    """Fake an 8-device host.  Must run BEFORE jax first initialises (jax
    locks the device count), which is why this module never imports jax at
    top level and why the pure helpers (``check_direction``, ``merge_rows``)
    stay importable from the test suite without touching the environment."""
    if f"device_count={N_DEVICES}" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={N_DEVICES}").strip()

from repro.train.timing import merge_rows, time_callable  # noqa: E402

ARTIFACT = os.path.join(os.path.dirname(__file__), "artifacts",
                        "BENCH_step.json")
# Full config identity: one row per distinct benchmark point, newest wins.
KEY_FIELDS = ("arch", "schedule", "pp", "dp", "tp", "sp", "ep", "zero",
              "n_chunks", "n_micro", "batch", "seq_len", "backend")

# (schedule, n_chunks, pp, dp, tp, sp, ep, zero) on 8 fake devices.  pp2
# legs are the CI smoke tier; pp4 legs complete the trajectory.  dualpipe
# shares each mesh; interleaved needs n_micro % pp == 0.  n_micro = 2·pp
# everywhere (``n_micro_for``): per-device micro_batch lands at 1, which
# keeps every schedule's chunk working set below the cache cliff (at
# mb=2 the 4-layer pp2 chunks go memory-bound and the remat replay —
# the very thing zb1p's split skips — becomes free, erasing the
# asymmetry under measurement) and is deep enough into steady state
# that the serialized overlapped model predicts zb1p strictly below
# 1f1b in every cell.  The pp4 schedule sweep runs sp=0 — dualpipe and
# interleaved execute 2× the chunk ops of 1f1b at half size, so SP's
# per-op gather/scatter collectives would bill them double fixed
# overhead and drown the schedule signal on this serializing host; the
# trailing sp=1 pair keeps the SP composition measured and gated where
# the op counts match (1f1b vs zb1p).
GRID = [
    ("1f1b",        1, 2, 2, 2, False, 1, "os"),
    ("zb1p",        1, 2, 2, 2, False, 1, "os"),
    ("dualpipe",    1, 2, 2, 2, False, 1, "os"),
    ("interleaved", 2, 2, 2, 2, False, 1, "os"),
    ("1f1b",        1, 4, 1, 2, False, 1, "os"),
    ("zb1p",        1, 4, 1, 2, False, 1, "os"),
    ("dualpipe",    1, 4, 1, 2, False, 1, "os"),
    ("interleaved", 2, 4, 1, 2, False, 1, "os"),
    ("1f1b",        1, 4, 1, 2, True,  1, "os"),
    ("zb1p",        1, 4, 1, 2, True,  1, "os"),
]

ARCH, BATCH, SEQ, N_LAYERS = "qwen2-1.5b", 8, 128, 8


def n_micro_for(pp: int) -> int:
    return 2 * pp


def host_serializes_ranks() -> bool:
    """True when this host cannot run the mesh's fake devices on distinct
    cores — XLA then executes the ranks' per-tick programs back-to-back,
    so measured wall clock tracks the SUM of per-rank active compute, not
    the max (``predict_step_time(serialize_ranks=...)``)."""
    return (os.cpu_count() or 1) < N_DEVICES


def host_cache_bytes() -> float:
    """Per-core private cache (largest data/unified level <= 2) from sysfs,
    0 when unreadable.  Feeds ``predict_step_time(cache_bytes=...)``: on a
    serializing host, zb1p's no-remat replay saving only materializes
    while the chunk vjp's saved intermediates stay L2-resident — measured
    here, 2-layer chunks (1.2 MB) fit a 2 MB L2 and keep the win, 4-layer
    chunks (2.5 MB) overflow it and tie with 1f1b."""
    best = 0.0
    base = "/sys/devices/system/cpu/cpu0/cache"
    try:
        entries = [e for e in os.listdir(base) if e.startswith("index")]
    except OSError:
        return 0.0
    for idx in entries:
        d = os.path.join(base, idx)
        try:
            with open(os.path.join(d, "level")) as f:
                level = int(f.read())
            with open(os.path.join(d, "type")) as f:
                kind = f.read().strip()
            if level > 2 or kind == "Instruction":
                continue
            with open(os.path.join(d, "size")) as f:
                size = f.read().strip()
        except (OSError, ValueError):
            continue
        mult = {"K": 2**10, "M": 2**20}.get(size[-1], 1)
        n = float(size[:-1] if size[-1] in "KM" else size) * mult
        best = max(best, n)
    return best


def _calibrate_peak_flops() -> float:
    """Achievable matmul FLOP/s on this host, measured the same way the
    steps are (warm, blocked, median-of-k).  MFU against an A100 peak is
    meaningless on CPU; against this calibration it is a real utilization
    number, and the calibration source is recorded in the row."""
    import jax
    import jax.numpy as jnp
    n = 1024
    x = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    r = time_callable(f, x, iters=5, warmup=2)
    return 2 * n**3 / r.median_s


def _calibrate_bandwidth() -> float:
    """Achievable streaming bytes/s (read+write of a 128 MiB buffer).
    ``predict_step_time``'s comm/flush terms are priced against this so the
    predicted compute:traffic ratio matches the machine being measured —
    at the nominal accelerator constants the zb1p flush term would be
    ~1000x overpriced relative to CPU matmul throughput and the predicted
    ranking would not be the one any real run of THIS harness produces."""
    import jax
    import jax.numpy as jnp
    n = 1 << 25
    x = jnp.ones((n,), jnp.float32)
    f = jax.jit(lambda a: a + 1.0)
    r = time_callable(f, x, iters=5, warmup=2)
    return 8 * n / r.median_s


def run_grid(grid, *, iters: int, out_path: str = ARTIFACT,
             quiet: bool = False,
             backend: str = "reference") -> List[Dict[str, Any]]:
    _ensure_fake_devices()
    import dataclasses
    import jax

    from repro.configs import get_spec
    from repro.core import (bubble_fraction, mfu, predict_step_time)
    from repro.core.parallel_config import RecomputePolicy, ZeROStage
    from repro.data.synthetic import config_for, make_batch
    from repro.models import build_model
    from repro.models.transformer import ModelOptions
    from repro.optim.adamw import init_train_state
    from repro.parallel.compat import make_mesh
    from repro.train.loop import TrainConfig
    from repro.train.pipeline_loop import make_pipeline_train_step
    from repro.train.schedules import build_exec_tables, make_schedule

    spec = dataclasses.replace(get_spec(ARCH, smoke=True), n_layers=N_LAYERS)
    # recompute=FULL: the documented chunk-recompute configuration.  The
    # fused backward then really replays the chunk inside its vjp (the 4F
    # the overlapped model prices), while zb1p's no-remat B stashes the
    # pending-dW instead of replaying — the asymmetry that lets zb1p win
    # measured.
    # ``backend`` keys the rows: "pallas" routes the chunk bodies through
    # the kernel fast path (interpret mode off-TPU — expect slower wall
    # clock there; the row exists to pin the trajectory, not to win on CPU)
    model = build_model(spec, ModelOptions(recompute=RecomputePolicy.FULL,
                                           backend=backend))
    state0 = init_train_state(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(config_for(spec, BATCH, SEQ), 0)
    peak = _calibrate_peak_flops()
    bw = _calibrate_bandwidth()
    tokens = BATCH * SEQ
    zmap = {"none": ZeROStage.NONE, "os": ZeROStage.OS,
            "os+g": ZeROStage.OS_G}

    rows: List[Dict[str, Any]] = []
    # Multiplicative calibration from each mesh cell's 1f1b row (first in
    # the grid per cell): the tiny smoke ops achieve a fixed fraction of
    # the 1024³-matmul calibrated peak, so the roofline underestimates
    # every schedule's active compute by roughly the same factor —
    # scaling each raw prediction by the cell's measured/raw 1f1b ratio
    # preserves the model's schedule *ratios* (what the direction gate
    # asserts) while making predicted_s the honest "what this harness
    # should measure" number.  (An additive per-tick overhead is the
    # wrong shape here: it bills zb1p's cheap cond-gated W flush ticks at
    # full dispatch cost and predicts the many-tick schedules slower than
    # they measure.)
    scale_by_cell: Dict[tuple, float] = {}
    for (schedule, n_chunks, pp, dp, tp, sp, ep, zero) in grid:
        n_micro = n_micro_for(pp)
        mesh = make_mesh((pp, dp, tp), ("pipe", "data", "model"))
        step = jax.jit(make_pipeline_train_step(
            model, TrainConfig(n_micro=n_micro), mesh,
            schedule=schedule, n_chunks=n_chunks, zero=zmap[zero],
            sp=sp, ep=ep))
        res = time_callable(step, state0, batch, iters=iters, warmup=2)
        # per-device micro-batch: the global batch splits over dp, then
        # into n_micro microbatches
        mb = max(BATCH // (dp * n_micro), 1)
        cell = (pp, dp, tp, sp)
        kw = dict(micro_batch=mb, seq_len=SEQ, n_chunks=n_chunks, tp=tp,
                  sp=sp, flops_per_s=peak, bytes_per_s=bw,
                  serialize_ranks=host_serializes_ranks(),
                  cache_bytes=host_cache_bytes())
        raw = predict_step_time(spec, schedule, pp, n_micro, **kw)
        if schedule == "1f1b" and cell not in scale_by_cell:
            scale_by_cell[cell] = res.median_s / raw.total_s
        scale = scale_by_cell.get(cell, 1.0)
        pred = raw
        tab = build_exec_tables(make_schedule(schedule, pp, n_micro,
                                              n_chunks=n_chunks))
        ticks_f = int((tab.f_act > 0).sum())
        ticks_b = int((tab.b_act > 0).sum())
        ticks_w = 0 if tab.w_act is None else int((tab.w_act > 0).sum())
        row = {
            "arch": ARCH, "schedule": schedule, "pp": pp, "dp": dp,
            "tp": tp, "sp": sp, "ep": ep, "zero": zero, "backend": backend,
            "n_chunks": n_chunks, "n_micro": n_micro,
            "batch": BATCH, "seq_len": SEQ, "n_layers": N_LAYERS,
            "median_s": res.median_s, "mean_s": res.mean_s,
            "min_s": res.min_s, "iters": iters,
            "warmup_s": res.warmup_s,
            "tokens_per_s": tokens / res.median_s,
            "mfu": mfu(res.median_s, spec, tokens, SEQ,
                       peak_flops_per_s=peak, n_devices=N_DEVICES),
            "peak_flops_per_s": peak,
            "bytes_per_s": bw,
            "peak_source": "calibrated_cpu_matmul_1024",
            "ideal_bubble_fraction": bubble_fraction(
                schedule, pp, n_micro, n_chunks),
            "predicted_s": raw.total_s * scale,
            "predicted_raw_s": raw.total_s,
            "predicted_scale": scale,
            "predicted_ticks": pred.ticks,
            "ticks_total": pred.ticks * pp,
            "ticks_active": pred.ticks_active,
            "ticks_f": ticks_f, "ticks_b": ticks_b, "ticks_w": ticks_w,
        }
        rows.append(row)
        if not quiet:
            print(f"{schedule:<12} pp{pp} tp{tp} sp={int(sp)} M{n_micro} "
                  f"median={res.median_s:.4f}s tok/s={row['tokens_per_s']:.0f} "
                  f"mfu={row['mfu']:.4f} bubble={row['ideal_bubble_fraction']:.3f} "
                  f"pred={raw.total_s * scale:.4f}s "
                  f"active={pred.ticks_active}/{pred.ticks * pp}")
    write_rows(rows, out_path)
    return rows


def write_rows(rows: List[Dict[str, Any]], path: str = ARTIFACT) -> None:
    existing: List[Dict[str, Any]] = []
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    # rows predating the backend key ran the jnp reference path — pin it
    # so they dedupe against fresh reference rows instead of coexisting
    for r in existing:
        r.setdefault("backend", "reference")
    merged = merge_rows(existing, rows, KEY_FIELDS)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
        f.write("\n")


def check_direction(rows: List[Dict[str, Any]], *,
                    min_gap: float = 0.10) -> List[str]:
    """Measured-vs-predicted ranking check (the CI gate).

    Within every (pp, tp, sp, n_micro, n_chunks, batch, seq) cell, any pair
    of schedules whose *predicted* step times differ by more than
    ``min_gap`` (relative) must measure in the same order.  Pairs inside
    the band are ties — either measured order passes — so CPU noise cannot
    flake the gate, but a real inversion (e.g. an executor regression that
    makes dualpipe slower than its tick count says) fails loudly.
    ``n_chunks`` is part of the cell: interleaved ticks run half-size
    chunks, so its per-tick overhead is not comparable to the full-chunk
    schedules' on an overhead-dominated CPU host — the gate covers the
    1f1b/zb1p/dualpipe trio, which shares chunk granularity.  Returns the
    violation messages (empty == pass).
    """
    cells: Dict[tuple, List[Dict[str, Any]]] = {}
    for r in rows:
        # full mesh identity: without dp/ep/zero in the key, rows from
        # different meshes (or ZeRO stages) would be ranked against each
        # other even though their measured times are not comparable
        cell = tuple(r.get(k) for k in
                     ("arch", "pp", "dp", "tp", "sp", "ep", "zero",
                      "n_micro", "n_chunks", "batch", "seq_len", "backend"))
        cells.setdefault(cell, []).append(r)
    bad: List[str] = []
    for cell, rs in cells.items():
        for i in range(len(rs)):
            for j in range(i + 1, len(rs)):
                a, b = rs[i], rs[j]
                pa, pb = a["predicted_s"], b["predicted_s"]
                if pa > pb:
                    a, b, pa, pb = b, a, pb, pa
                if pb <= pa * (1 + min_gap):
                    continue          # predicted tie: either order is fine
                if a["median_s"] > b["median_s"]:
                    bad.append(
                        f"cell {cell}: predicted {a['schedule']}"
                        f" ({pa:.4f}s) < {b['schedule']} ({pb:.4f}s) by"
                        f" >{min_gap:.0%}, but measured"
                        f" {a['median_s']:.4f}s > {b['median_s']:.4f}s")
    return bad


def check_convergence(rows: List[Dict[str, Any]], *,
                      tie: float = 0.10) -> List[str]:
    """The overlap gate (CI's ``step-bench-smoke`` convergence check).

    Two assertions over the artifact rows:

    * in every cell holding both a ``1f1b`` and a ``zb1p`` measurement,
      measured zb1p must not exceed measured 1f1b by more than the ``tie``
      band — the cond-gated W ticks and the no-remat B/W split must keep
      zero-bubble at least competitive wherever the model calls it a tie,
      and strictly ahead where it predicts a win;
    * every pp>1 row must report ``ticks_active < ticks_total`` — the
      engine is actually skipping idle rank-ticks (a regression to masked
      always-on compute shows up here before it shows up as wall clock).

    Returns violation messages (empty == pass).  Rows predating the
    overlap engine (no ``ticks_active``) fail the second check loudly
    rather than passing silently.
    """
    bad: List[str] = []
    cells: Dict[tuple, Dict[str, Dict[str, Any]]] = {}
    for r in rows:
        cell = tuple(r.get(k) for k in
                     ("arch", "pp", "dp", "tp", "sp", "ep", "zero",
                      "n_micro", "batch", "seq_len", "backend"))
        cells.setdefault(cell, {})[r["schedule"]] = r
    for cell, by_sched in cells.items():
        if "1f1b" in by_sched and "zb1p" in by_sched:
            base = by_sched["1f1b"]["median_s"]
            zb = by_sched["zb1p"]["median_s"]
            if zb > base * (1 + tie):
                bad.append(
                    f"cell {cell}: measured zb1p {zb:.4f}s exceeds 1f1b "
                    f"{base:.4f}s by more than the {tie:.0%} tie band")
    for r in rows:
        if r.get("pp", 1) <= 1:
            continue
        total, active = r.get("ticks_total"), r.get("ticks_active")
        if total is None or active is None:
            bad.append(f"{r.get('schedule')} pp{r.get('pp')}: row lacks "
                       "ticks_total/ticks_active (pre-overlap artifact?)")
        elif not active < total:
            bad.append(
                f"{r.get('schedule')} pp{r.get('pp')} M{r.get('n_micro')}: "
                f"ticks_active {active} >= ticks_total {total} — the "
                "overlap engine is not skipping any idle rank-ticks")
    return bad


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="pp2-only tier (CI): 1f1b/dualpipe/zb1p/interleaved")
    ap.add_argument("--iters", type=int, default=5,
                    help="timed windows per config (median reported)")
    ap.add_argument("--out", default=ARTIFACT)
    ap.add_argument("--check-direction", action="store_true",
                    help="assert measured ranking matches the executor-model "
                         "ranking in the artifact (no new measurements)")
    ap.add_argument("--check-convergence", action="store_true",
                    help="assert measured zb1p <= 1f1b within the tie band "
                         "and ticks_active < ticks_total on every pp>1 row "
                         "(no new measurements)")
    ap.add_argument("--min-gap", type=float, default=0.10,
                    help="relative predicted gap below which a pair is a tie")
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"],
                    help="kernel backend the measured steps run "
                         "(rows are keyed on it; 'pallas' is interpret-mode "
                         "off-TPU — slower wall clock there by design)")
    args = ap.parse_args(argv)

    if args.check_direction or args.check_convergence:
        if not os.path.exists(args.out):
            print(f"no artifact at {args.out}; run the bench first",
                  file=sys.stderr)
            return 2
        with open(args.out) as f:
            rows = json.load(f)
        bad = []
        if args.check_direction:
            bad += check_direction(rows, min_gap=args.min_gap)
        if args.check_convergence:
            bad += check_convergence(rows, tie=args.min_gap)
        for msg in bad:
            print(f"DIRECTION VIOLATION: {msg}", file=sys.stderr)
        print(f"direction check: {len(rows)} rows, "
              f"{len(bad)} violations")
        return 1 if bad else 0

    grid = [g for g in GRID if g[2] == 2] if args.smoke else GRID
    rows = run_grid(grid, iters=args.iters, out_path=args.out,
                    backend=args.backend)
    print(f"wrote {len(rows)} rows -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
