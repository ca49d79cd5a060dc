#!/usr/bin/env python3
"""Chip smoke test: the 3D training executor on a TPU at published widths.

  python3 chip_smoke.py                  # one chip: device, kernels, train
  python3 chip_smoke.py --chips 4        # a four-chip host: the sharded legs
  JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse [--chips 4]

Phases on one chip (``devices[0]``), by default:

* device  — fails unless JAX's first device is a TPU; never falls back.
* kernels — rmsnorm, flash attention (qwen2-1.5b GQA; MLA with dq=192,
  dv=128) and the grouped expert matmul (olmoe-1b-7b widths) against
  their jnp oracles in ``repro.kernels.ref``.
* train   — qwen2-1.5b at published widths cut to 8 layers, through
  ``make_pipeline_train_step`` on a (pipe=1, data=1, model=1) mesh with
  ``backend="pallas"`` and AdamW, on synthetic data from ``--seed``.  The
  compiled step must hold the kernels (``tpu_custom_call``), the loss must
  be finite and fall, and the step-0 loss must match the same step with
  ``backend="reference"``.  Prints the warm step time, the device's
  ``peak_bytes_in_use`` and what ``estimate_memory`` predicts.

``--chips 4`` runs only the sharded legs, each against its one-chip
reference on ``devices[0]`` of the same process:

* dense — the 8-layer qwen2-1.5b at pp2·tp2 with ``sp=True`` under
  ``1f1b`` and ``zb1p``;
* moe   — olmoe-1b-7b at published widths cut to 1 layer, at pp1·dp2·tp2
  with ``ep=2`` and ZeRO ``os+g``.

``--rehearse`` runs the same phases on the CPU at smoke widths, with the
kernels in interpret mode (and four virtual devices for ``--chips 4``);
it ends with a ``rehearsal_ok`` line, never an ``ok`` line.

On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Any failure raises and exits non-zero.  Everything runs in this one
process: a child process could not reach a chip this process holds.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
import time

AXES = ("pipe", "data", "model")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Shapes of every phase: published widths on the chip, smoke widths
    in the CPU rehearsal."""
    smoke: bool
    dense_layers: int        # qwen2-1.5b cut in depth only
    moe_layers: int          # olmoe-1b-7b cut in depth only
    seq: int                 # train phase and moe leg
    dense_seq: int           # dense leg (its one-chip reference holds M=2)
    # kernels phase
    norm_rows: int
    attn_seq: int
    gqa: tuple               # (n_h, dq, dv)
    mla: tuple
    gmm: tuple               # (E, C, h, f)


CHIP = Sizes(smoke=False, dense_layers=8, moe_layers=1, seq=2048,
             dense_seq=1024, norm_rows=2048, attn_seq=4096,
             gqa=(12, 128, 128), mla=(16, 192, 128),
             gmm=(64, 640, 2048, 1024))
REHEARSAL = Sizes(smoke=True, dense_layers=2, moe_layers=1, seq=128,
                  dense_seq=128, norm_rows=256, attn_seq=256,
                  gqa=(4, 64, 64), mla=(2, 192, 128),
                  gmm=(4, 128, 256, 128))

# kernels: the worst row's ||kernel - ref|| / ||ref||, a row being one
# output vector (a token's normed hidden, one head of one query, one
# expert row).  Row-wise, so the small outputs of flash rows deep in the
# sequence are held to the same relative bound as the large early ones.
# Rounding the output to bf16 alone costs up to 2**-9 per element.
KERNEL_REL = 2.0 ** -6
# sharded legs: the step-0 loss bands of the CPU tests
# (tests/test_sp_equivalence.py, tests/test_ep_equivalence.py), and each
# first-moment leaf's ||m - m_ref|| / ||m_ref||.  After one AdamW step m is
# (1 - beta1) times the gradient, so this compares every sharded gradient
# elementwise with the one-chip one.  (The master params cannot serve: one
# step moves each element by at most about lr, so two runs differ by at
# most about 2 lr whatever their gradients.)
LOSS_TOL = {"dense": 5e-3, "moe": 1e-1}
MOMENT_REL = 1e-1
# microbatches (of one sequence each) in the dense leg: the one-chip
# reference's fp32 grad accumulator and activations at M=2, s=1024 fit
# 16 GB (at s=2048 they do not)
DENSE_MICRO = 2
# train-phase steps: step 0 compiles and is compared with the reference
# backend, the rest are timed warm
TRAIN_STEPS = 4
# a warmup-scale learning rate: AdamW's first steps from a random init at
# the default 3e-4 overshoot at a 152k vocabulary (on one v5e chip:
# 12.31, 10.41, 14.20, 12.57), so the loss would not fall over 4 steps
TRAIN_LR = 3e-5
# the step-0 loss of backend="pallas" vs "reference": one bf16 ulp of it
BF16_REL = 2.0 ** -8


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at smoke widths (interpret mode)")
    return ap.parse_args()


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(args):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log("device", f"platform={d0.platform} kind={d0.device_kind} "
                  f"count={len(devs)}")
    if not args.rehearse and d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is a "
                         f"{d0.platform!r} device, not a TPU")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _assert_close(tag, got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{tag}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{tag}: non-finite kernel output")
    d = (got - want).reshape(-1, got.shape[-1])
    ref = want.reshape(-1, want.shape[-1])
    rel = np.linalg.norm(d, axis=1) / np.maximum(
        np.linalg.norm(ref, axis=1), np.finfo(np.float32).tiny)
    worst = int(np.argmax(rel))
    log("kernels", f"{tag}: shape={got.shape} worst row {worst}: "
                   f"||kernel-ref||/||ref||={rel[worst]:.3e} "
                   f"(median row {float(np.median(rel)):.3e})")
    if not rel[worst] <= KERNEL_REL:
        raise AssertionError(f"{tag}: row {worst} differs from the "
                             f"reference by {rel[worst]:.3e} relative "
                             f"(> {KERNEL_REL})")


def kernels_phase(sz: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as K
    from repro.kernels import ref as R

    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    normal = lambda shape, scale=1.0: (
        scale * jax.random.normal(next(ks), shape, jnp.float32)
    ).astype(jnp.bfloat16)

    h = 1536                                   # qwen2-1.5b d_model
    x, w = normal((sz.norm_rows, h)), 1.0 + normal((h,), 0.1)
    _assert_close(f"rmsnorm h={h}", K.rmsnorm(x, w), R.rmsnorm_ref(x, w))

    for tag, (n_h, dq, dv) in (("flash gqa", sz.gqa), ("flash mla", sz.mla)):
        s = sz.attn_seq
        q, k = normal((1, s, n_h, dq)), normal((1, s, n_h, dq))
        v = normal((1, s, n_h, dv))
        scale = dq ** -0.5
        _assert_close(f"{tag} s={s} n_h={n_h} dq={dq} dv={dv}",
                      K.flash_attention(q, k, v, scale=scale),
                      R.flash_attention_ref(q, k, v, scale=scale))

    E, C, hd, f = sz.gmm
    lhs, rhs = normal((E * C, hd)), normal((E, hd, f), hd ** -0.5)
    emap = np.repeat(np.arange(E, dtype=np.int32), C // 128)
    _assert_close(f"gmm E={E} C={C} h={hd} f={f}",
                  K.gmm(lhs, rhs, jnp.asarray(emap)),
                  R.gmm_ref(lhs, rhs, emap, block_m=128))


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _spec(name: str, layers: int, sz: Sizes):
    from repro.configs import get_spec
    return dataclasses.replace(get_spec(name, smoke=sz.smoke),
                               n_layers=layers)


def _placed_state(model, mesh, zero, ep: int, seed: int):
    """TrainState from ``seed``, built straight into the executor's state
    layout on ``mesh`` (random draws do not depend on the sharding)."""
    import jax
    from repro.optim.adamw import init_train_state
    from repro.parallel.sharding import state_shardings
    from repro.train import pipeline_loop as PL

    init = lambda k: init_train_state(model.init(k))
    key = jax.random.PRNGKey(seed)
    rules = PL._EXEC_EP_RULES if ep > 1 else PL._EXEC_TP_RULES
    sh = state_shardings(jax.eval_shape(init, key), mesh, zero, rules=rules)
    return jax.jit(init, out_shardings=sh)(key)


def _placed_batch(spec, batch: int, seq: int, mesh, seed: int, step: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.data.synthetic import config_for, make_batch
    b = make_batch(config_for(spec, batch, seq, seed=seed), step)
    return jax.device_put(b, NamedSharding(mesh, P()))


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.3f} GiB"


# ---------------------------------------------------------------------------
# train (one chip)
# ---------------------------------------------------------------------------

def train_phase(sz: Sizes, dev, seed: int) -> None:
    import jax
    from repro.core.memory_model import estimate_memory
    from repro.core.parallel_config import ParallelConfig, ZeROStage
    from repro.models import build_model
    from repro.models.transformer import ModelOptions
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.compat import make_mesh
    from repro.train.loop import TrainConfig
    from repro.train.pipeline_loop import make_pipeline_train_step

    spec = _spec("qwen2-1.5b", sz.dense_layers, sz)
    mesh = make_mesh((1, 1, 1), AXES, devices=[dev])
    model = build_model(spec, ModelOptions(backend="pallas"))
    log("train", f"{spec.name} layers={spec.n_layers} h={spec.h} "
                 f"heads={spec.n_h}/{spec.n_kv} d_ff={spec.h_ff} "
                 f"vocab={spec.vocab} "
                 f"params={spec.total_params() / 1e9:.3f}B b=1 s={sz.seq} "
                 "backend=pallas mesh=(1,1,1)")
    batches = [_placed_batch(spec, 1, sz.seq, mesh, seed, i)
               for i in range(TRAIN_STEPS)]
    state = _placed_state(model, mesh, ZeROStage.NONE, 1, seed)

    train_cfg = TrainConfig(adamw=AdamWConfig(lr=TRAIN_LR))
    step = jax.jit(make_pipeline_train_step(model, train_cfg, mesh),
                   donate_argnums=0)
    t0 = time.perf_counter()
    compiled = step.lower(state, batches[0]).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    log("train", f"compiled in {time.perf_counter() - t0:.1f}s; "
                 f"tpu_custom_call x{n_kernels} in the compiled pallas step")
    if dev.platform == "tpu" and n_kernels == 0:
        raise AssertionError("the compiled pallas step holds no "
                             "tpu_custom_call: the kernels did not lower")

    losses, times = [], []
    for i, b in enumerate(batches):
        t = time.perf_counter()
        state, metrics = compiled(state, b)
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
        log("train", f"step {i} loss={losses[-1]:.6f} "
                     f"grad_norm={float(metrics['grad_norm']):.4f} "
                     f"wall={times[-1]:.4f}s")
    if not all(map(math.isfinite, losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    warm = times[1:]
    log("train", f"warm step time (block_until_ready, {len(warm)} steps): "
                 f"median={statistics.median(warm):.4f}s "
                 f"all={[round(t, 4) for t in warm]}")

    peak = _peak_bytes(dev)
    cfg = ParallelConfig(micro_batch=1, seq_len=sz.seq, attn_impl="flash")
    est = estimate_memory(spec, cfg)
    log("train", f"peak_bytes_in_use={_gib(peak)} vs estimate_memory "
                 f"total={_gib(est.total)} ({cfg.describe()}; "
                 + ", ".join(f"{k}={_gib(v)}"
                             for k, v in est.breakdown().items()
                             if k != "total")
                 + ") — one chip run")
    ma = compiled.memory_analysis()
    log("train", "compiled step memory_analysis: "
                 f"args={_gib(ma.argument_size_in_bytes)} "
                 f"outputs={_gib(ma.output_size_in_bytes)} "
                 f"aliased={_gib(ma.alias_size_in_bytes)} "
                 f"temps={_gib(ma.temp_size_in_bytes)}; "
                 f"memory_stats={dev.memory_stats()}")
    del state

    ref_model = build_model(spec, ModelOptions(backend="reference"))
    ref_step = make_pipeline_train_step(ref_model, train_cfg, mesh)
    state0 = _placed_state(ref_model, mesh, ZeROStage.NONE, 1, seed)
    loss_ref = float(jax.jit(lambda st, b: ref_step(st, b)[1]["loss"])(
        state0, batches[0]))
    dl = abs(losses[0] - loss_ref)
    log("train", f"step-0 loss pallas={losses[0]:.6f} "
                 f"reference={loss_ref:.6f} |diff|={dl:.3e}")
    if not dl <= BF16_REL * abs(loss_ref):
        raise AssertionError(f"step-0 loss differs from the reference "
                             f"backend by {dl} (> {BF16_REL} x loss)")


# ---------------------------------------------------------------------------
# four chips: sharded legs vs one-chip references
# ---------------------------------------------------------------------------

def _run_step(model, mesh, batch, seed, *, n_micro, zero, ep=1, **kw):
    """One executor step from the seeded state; returns the host-side loss
    and first-moment leaves, plus each device's bytes in use while the
    step's outputs are alive."""
    import jax
    import numpy as np
    from repro.train.loop import TrainConfig
    from repro.train.pipeline_loop import make_pipeline_train_step

    state = _placed_state(model, mesh, zero, ep, seed)
    step = make_pipeline_train_step(model, TrainConfig(n_micro=n_micro),
                                    mesh, zero=zero, ep=ep, **kw)
    new, metrics = jax.jit(step, donate_argnums=0)(state, batch)
    jax.block_until_ready((new, metrics))
    in_use = [d.memory_stats()["bytes_in_use"] if d.memory_stats() else None
              for d in mesh.devices.flat]
    out = {"loss": float(metrics["loss"]),
           "m": [np.asarray(a) for a in jax.tree.leaves(new.m)],
           "bytes_in_use": in_use}
    del new, metrics
    return out


def _compare(tag, ref, got, tol_loss) -> None:
    import numpy as np
    dl = abs(ref["loss"] - got["loss"])
    rel = []
    for a, b in zip(ref["m"], got["m"]):
        a, b = a.astype(np.float32), b.astype(np.float32)
        na = float(np.linalg.norm(a))
        rel.append(float(np.linalg.norm(b - a)) / na if na > 0
                   else float(np.linalg.norm(b)))
    worst = int(np.argmax(rel))
    used = got["bytes_in_use"]
    log(tag, f"loss={got['loss']:.6f} ref={ref['loss']:.6f} |dloss|={dl:.3e} "
             f"first moment: worst leaf {worst} of {len(rel)} "
             f"||m-m_ref||/||m_ref||={rel[worst]:.3e} "
             f"(median leaf {float(np.median(rel)):.3e}) "
             f"bytes_in_use per device={[_gib(u) for u in used]}")
    if not (dl < tol_loss and rel[worst] < MOMENT_REL):
        raise AssertionError(f"{tag}: sharded step diverged from its "
                             f"one-chip reference")
    if None not in used and min(used) < 0.25 * max(used):
        raise AssertionError(f"{tag}: device memory is lopsided ({used}); "
                             "shards are piling onto one device")


def dense_leg(sz: Sizes, devs, seed: int) -> None:
    from repro.core.parallel_config import ZeROStage
    from repro.models import build_model
    from repro.models.transformer import ModelOptions
    from repro.parallel.compat import make_mesh

    spec = _spec("qwen2-1.5b", sz.dense_layers, sz)
    model = build_model(spec, ModelOptions(backend="pallas"))
    n_micro = DENSE_MICRO
    log("dense", f"{spec.name} layers={spec.n_layers} b={n_micro} "
                 f"s={sz.dense_seq} n_micro={n_micro} backend=pallas")
    mesh4 = make_mesh((2, 1, 2), AXES, devices=devs)
    mesh1 = make_mesh((1, 1, 1), AXES, devices=devs[:1])
    runs = {}
    for sched in ("1f1b", "zb1p"):
        batch = _placed_batch(spec, n_micro, sz.dense_seq, mesh4, seed, 0)
        runs[sched] = _run_step(model, mesh4, batch, seed, n_micro=n_micro,
                                zero=ZeROStage.NONE, schedule=sched, sp=True)
    batch = _placed_batch(spec, n_micro, sz.dense_seq, mesh1, seed, 0)
    ref = _run_step(model, mesh1, batch, seed, n_micro=n_micro,
                    zero=ZeROStage.NONE)
    for sched, got in runs.items():
        _compare(f"dense pp2·tp2·sp {sched}", ref, got, LOSS_TOL["dense"])


def moe_leg(sz: Sizes, devs, seed: int) -> None:
    from repro.core.parallel_config import ZeROStage
    from repro.models import build_model
    from repro.models.transformer import ModelOptions
    from repro.parallel.compat import make_mesh

    spec = _spec("olmoe-1b-7b", sz.moe_layers, sz)
    # Each data shard applies its own capacity, so any dropped token makes
    # the sharded and one-chip steps train on different tokens.  At a
    # capacity factor of n_routed / n_active every expert can take every
    # token (C = T), so neither drops one.  C is then 4096 on one chip and
    # 2048 per EP rank, both multiples of 128.
    model = build_model(spec, ModelOptions(
        backend="pallas",
        capacity_factor=spec.moe.n_routed / spec.moe.n_active))
    log("moe", f"{spec.name} layers={spec.n_layers} h={spec.h} "
               f"experts={spec.moe.n_routed} top-{spec.moe.n_active} "
               f"params={spec.total_params() / 1e9:.3f}B b=2 s={sz.seq} "
               "backend=pallas")
    mesh4 = make_mesh((1, 2, 2), AXES, devices=devs)
    mesh1 = make_mesh((1, 1, 1), AXES, devices=devs[:1])
    batch = _placed_batch(spec, 2, sz.seq, mesh4, seed, 0)
    got = _run_step(model, mesh4, batch, seed, n_micro=1,
                    zero=ZeROStage.OS_G, ep=2)
    batch = _placed_batch(spec, 2, sz.seq, mesh1, seed, 0)
    ref = _run_step(model, mesh1, batch, seed, n_micro=1,
                    zero=ZeROStage.NONE)
    _compare("moe pp1·dp2·tp2·ep2 os+g", ref, got, LOSS_TOL["moe"])


# ---------------------------------------------------------------------------

def main() -> None:
    args = parse_args()
    if args.rehearse and args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    devs = device_phase(args)
    log("device", f"compile cache: {enable_compile_cache()}")
    sz = REHEARSAL if args.rehearse else CHIP
    t0 = time.perf_counter()
    if args.chips == 1:
        kernels_phase(sz, args.seed)
        train_phase(sz, devs[0], args.seed)
    else:
        dense_leg(sz, devs[:4], args.seed)
        moe_leg(sz, devs[:4], args.seed)
    log("done", f"all phases passed in {time.perf_counter() - t0:.1f}s")
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    key = "rehearsal_ok" if args.rehearse else "ok"
    print(json.dumps({key: True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
