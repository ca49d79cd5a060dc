"""Pluggable pipeline schedules: the runtime-facing API.

The schedule *abstraction* — per-rank tick emission (which microbatch, which
virtual-stage chunk, forward or backward, where the boundary tensors travel)
— lives in :mod:`repro.core.schedules` so the analytic memory model can
consume it without importing the runtime; this module re-exports it and adds
the one runtime-specific piece: :func:`build_exec_tables`, which compiles a
:class:`~repro.core.schedules.PipelineSchedule` into the static numpy tables
the SPMD executor (``train.pipeline_loop``) indexes with
``lax.axis_index('pipe')`` inside its tick scan.

Executor timeline vs canonical timeline
---------------------------------------

Canonical ticks (``PipelineSchedule.ticks``) are one op per rank per tick —
the unit the in-flight accounting uses.  The executor instead pairs one
cond-gated forward with one cond-gated backward per tick (plus, for
schedules that split the backward, a dedicated cond-gated W tick that never
shares a rank-tick with the rank's own F or B), so ``build_exec_tables``
re-times the same per-rank op order under that capacity via
``core.schedules.exec_tick_times`` and then derives:

* per-tick forward/backward tables: is the rank active, which microbatch,
  which local chunk, which buffer slot;
* boundary routing: whether the rank's forward output / input-gradient
  travels down-ring (rank r → r+1, the 1f1b/interleaved direction; also
  interleaved's wraparound pp-1 → 0 between virtual stages) or up-ring
  (dualpipe's reverse direction), and where the *receiving* rank must store
  the payload;
* buffer slot assignments: boundary inputs (and arriving gradients) are
  kept in per-chunk slot rings; slots are assigned by greedy interval
  colouring over each value's residency window, so the ring size **is** the
  executor's true in-flight bound for that (rank, chunk) — the quantity the
  schedule-aware memory model estimates.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from repro.core.schedules import (SCHEDULES, PipelineSchedule, TickOp,
                                  exec_tick_times, make_schedule,
                                  n_model_chunks, schedule_placement)

__all__ = ["SCHEDULES", "PipelineSchedule", "TickOp", "ExecTables",
           "build_exec_tables", "forward_runs", "make_schedule",
           "n_model_chunks", "schedule_placement"]


@dataclasses.dataclass(frozen=True)
class ExecTables:
    """Static (T, pp) executor tables; ``*_idx`` entries are flat buffer
    indices ``chunk * slots_per_chunk + slot``.  Inactive entries hold 0 and
    are masked by the matching ``*_act`` table."""

    schedule: str
    pp: int
    n_chunks: int
    n_micro: int
    n_stages: int
    T: int
    x_slots: int            # boundary-input slots per chunk
    g_slots: int            # gradient slots per chunk
    # forward compute
    f_act: np.ndarray
    f_micro: np.ndarray
    f_chunk: np.ndarray
    f_xidx: np.ndarray
    # backward compute
    b_act: np.ndarray
    b_micro: np.ndarray
    b_chunk: np.ndarray
    b_xidx: np.ndarray
    b_gidx: np.ndarray
    # sends (sender side, end of tick): does this rank's fwd out / grad out
    # travel down-ring (r -> r+1 mod pp) or up-ring (r -> r-1 mod pp)?
    fsend_down: np.ndarray
    fsend_up: np.ndarray
    bsend_down: np.ndarray
    bsend_up: np.ndarray
    # receives (receiver side, end of tick): store the arriving payload at
    # the flat buffer index
    rfd_act: np.ndarray     # fwd payload via down-ring
    rfd_idx: np.ndarray
    rfu_act: np.ndarray     # fwd payload via up-ring
    rfu_idx: np.ndarray
    rgd_act: np.ndarray     # grad payload via down-ring
    rgd_idx: np.ndarray
    rgu_act: np.ndarray     # grad payload via up-ring
    rgu_idx: np.ndarray
    # deferred weight-gradient application (zb1p's W ops; all-zero
    # otherwise): B runs the chunk vjp once (no slot checkpointing — the
    # split stashes grads instead of recomputing activations) and writes
    # the fp32 pending-dW into stash slot ``b_sidx``; at tick t rank r's W
    # op flushes stash slot ``w_sidx`` into the grad accumulator for
    # (``w_micro``, ``w_chunk``).  ``s_slots`` is the stash ring depth per
    # (rank, chunk) — the interval colouring of the B→W pendency windows,
    # whose peak is ``core.schedules.zb_pending_peak`` (what the memory
    # model prices; see train.pipeline_loop)
    w_act: np.ndarray = None
    w_micro: np.ndarray = None
    w_chunk: np.ndarray = None
    b_sidx: np.ndarray = None
    w_sidx: np.ndarray = None
    s_slots: int = 1


def _color_intervals(intervals: List[Tuple[int, int, int]]) -> Dict[int, int]:
    """Greedy interval colouring: micro -> slot, with [start, end) windows
    (a write landing exactly when the previous occupant is released may
    reuse its slot — the executor writes arrivals after the tick's reads)."""
    out: Dict[int, int] = {}
    free_at: List[int] = []
    for start, end, m in sorted(intervals):
        for s, f in enumerate(free_at):
            if f <= start:
                free_at[s] = end
                out[m] = s
                break
        else:
            out[m] = len(free_at)
            free_at.append(end)
    return out


def build_exec_tables(sched: PipelineSchedule) -> ExecTables:
    pp, v, G, M = sched.pp, sched.n_chunks, sched.n_stages, sched.n_micro
    times = exec_tick_times(sched)
    T = max(times.values()) + 1
    own = [[sched.owner(g, m) for g in range(G)] for m in range(M)]
    tF = {(m, g): times[("F", m, g)] for m in range(M) for g in range(G)}
    tB = {(m, g): times[("B", m, g)] for m in range(M) for g in range(G)}
    tW = {(m, g): times[("W", m, g)] for m in range(M) for g in range(G)
          if ("W", m, g) in times}

    # --- buffer slot assignment (per rank-chunk interval colouring) -------
    # A slot is held until its last reader, the B tick (zb1p's W op reads
    # the grad stash, not the x/g rings — B is still the rings' last
    # reader).  The stash gets its own colouring over the B→W pendency
    # windows; its per-(rank, chunk) peak is core.schedules.zb_pending_peak,
    # which is what the memory model prices for zb1p.
    xiv: Dict[Tuple[int, int], List[Tuple[int, int, int]]] = {}
    giv: Dict[Tuple[int, int], List[Tuple[int, int, int]]] = {}
    siv: Dict[Tuple[int, int], List[Tuple[int, int, int]]] = {}
    for m in range(M):
        for g in range(G):
            r, c = own[m][g]
            t_rel = tB[(m, g)]                      # last read releases slot
            if g > 0:       # boundary input arrives when upstream F finishes
                xiv.setdefault((r, c), []).append(
                    (tF[(m, g - 1)], t_rel, m))
            if g < G - 1:   # cotangent arrives when downstream B finishes
                giv.setdefault((r, c), []).append(
                    (tB[(m, g + 1)], t_rel, m))
            if (m, g) in tW:    # pending-dW lives from its B to its W tick
                siv.setdefault((r, c), []).append(
                    (tB[(m, g)], tW[(m, g)], m))
    xslot = {rc: _color_intervals(iv) for rc, iv in xiv.items()}
    gslot = {rc: _color_intervals(iv) for rc, iv in giv.items()}
    sslot = {rc: _color_intervals(iv) for rc, iv in siv.items()}
    xs = max([max(sl.values()) + 1 for sl in xslot.values()] or [1])
    gs = max([max(sl.values()) + 1 for sl in gslot.values()] or [1])
    ss = max([max(sl.values()) + 1 for sl in sslot.values()] or [1])

    def z(dtype=np.int32):
        return np.zeros((T, pp), dtype)

    f_act, f_micro, f_chunk, f_xidx = z(np.float32), z(), z(), z()
    b_act, b_micro, b_chunk, b_xidx, b_gidx = z(np.float32), z(), z(), z(), z()
    fsd, fsu, bsd, bsu = z(np.float32), z(np.float32), z(np.float32), \
        z(np.float32)
    rfd_a, rfd_i, rfu_a, rfu_i = z(np.float32), z(), z(np.float32), z()
    rgd_a, rgd_i, rgu_a, rgu_i = z(np.float32), z(), z(np.float32), z()
    w_act, w_micro, w_chunk, b_si, w_si = \
        z(np.float32), z(), z(), z(), z()

    for m in range(M):
        for g in range(G):
            r, c = own[m][g]
            t = tF[(m, g)]
            f_act[t, r] = 1.0
            f_micro[t, r] = m
            f_chunk[t, r] = c
            f_xidx[t, r] = c * xs + (xslot[(r, c)][m] if g > 0 else 0)
            if g < G - 1:
                r2, c2 = own[m][g + 1]
                down = (r2 - r) % pp == 1
                (fsd if down else fsu)[t, r] = 1.0
                a, i = (rfd_a, rfd_i) if down else (rfu_a, rfu_i)
                a[t, r2] = 1.0
                i[t, r2] = c2 * xs + xslot[(r2, c2)][m]

            t = tB[(m, g)]
            b_act[t, r] = 1.0
            b_micro[t, r] = m
            b_chunk[t, r] = c
            b_xidx[t, r] = c * xs + (xslot[(r, c)][m] if g > 0 else 0)
            b_gidx[t, r] = c * gs + (gslot[(r, c)][m] if g < G - 1 else 0)
            if (m, g) in tW:
                b_si[t, r] = c * ss + sslot[(r, c)][m]
            if g > 0:
                r2, c2 = own[m][g - 1]
                down = (r2 - r) % pp == 1
                (bsd if down else bsu)[t, r] = 1.0
                a, i = (rgd_a, rgd_i) if down else (rgu_a, rgu_i)
                a[t, r2] = 1.0
                i[t, r2] = c2 * gs + gslot[(r2, c2)][m]

            if (m, g) in tW:
                t = tW[(m, g)]
                w_act[t, r] = 1.0
                w_micro[t, r] = m
                w_chunk[t, r] = c
                w_si[t, r] = c * ss + sslot[(r, c)][m]

    return ExecTables(
        schedule=sched.name, pp=pp, n_chunks=v, n_micro=M, n_stages=G, T=T,
        x_slots=xs, g_slots=gs,
        f_act=f_act, f_micro=f_micro, f_chunk=f_chunk, f_xidx=f_xidx,
        b_act=b_act, b_micro=b_micro, b_chunk=b_chunk, b_xidx=b_xidx,
        b_gidx=b_gidx,
        fsend_down=fsd, fsend_up=fsu, bsend_down=bsd, bsend_up=bsu,
        rfd_act=rfd_a, rfd_idx=rfd_i, rfu_act=rfu_a, rfu_idx=rfu_i,
        rgd_act=rgd_a, rgd_idx=rgd_i, rgu_act=rgu_a, rgu_idx=rgu_i,
        w_act=w_act, w_micro=w_micro, w_chunk=w_chunk,
        b_sidx=b_si, w_sidx=w_si, s_slots=ss)


def forward_runs(tab: ExecTables, last_flag: np.ndarray) -> np.ndarray:
    """(T, pp) float table of the forward ticks the executor runs as ticks
    of their own: ``tab.f_act`` without the last model chunk's forwards
    (``last_flag`` is the partition's (pp, v) flag).  That chunk's forward
    is run by the same microbatch's backward tick, whose vjp computes it
    anyway, so its F tick would only repeat it.

    Raises where a send or receive table is active for the output of a
    forward this drops: its payload would be zeros, not the activation.  No
    schedule sends the last chunk's output anywhere, since nothing consumes
    it."""
    ranks = np.arange(tab.pp)[None, :]
    f_run = tab.f_act * (1.0 - last_flag[ranks, tab.f_chunk])
    dropped = (tab.f_act > 0.5) & (f_run < 0.5)
    # sender view of the receives: down-ring lands on rank r + 1, up-ring
    # on rank r - 1, in the tick the forward runs
    sent = (tab.fsend_down + tab.fsend_up
            + np.roll(tab.rfd_act, -1, axis=1)
            + np.roll(tab.rfu_act, 1, axis=1))
    bad = np.argwhere(dropped & (sent > 0.5))
    if bad.size:
        t, r = bad[0]
        raise ValueError(
            f"{tab.schedule}: the last model chunk's forward at tick {t} on "
            f"rank {r} is sent on, so its F tick cannot be dropped")
    return f_run
