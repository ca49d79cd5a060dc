"""Named scopes of the pipeline executor's train step.

The executor (``train.pipeline_loop``) enters each of these with
``jax.named_scope`` at a layer boundary.  A scope only labels the ops'
metadata (``op_name`` in the compiled HLO, e.g.
``jit(step)/…/tick.B/…/transpose(jvp(attention))/…``); it changes no
instruction, kernel or fusion name, so a profile of the step can be read
per layer and per phase:

* ``tick.F`` / ``tick.B`` / ``tick.W``: the schedule's forward, backward
  and (zb1p) weight-gradient ticks.  Under ``tick.B`` the chunk's forward
  runs again inside ``jax.vjp`` — its ops carry ``jvp(<scope>)`` — before
  the backward, whose ops carry ``transpose(jvp(<scope>))``.  The last
  model chunk has no ``tick.F``: its forward runs only there (at pp = 1,
  every chunk's);
* the layers below, one per op; the innermost wins where they nest: a
  slot's ``attention`` and ``mlp`` sit inside ``layer_scan`` (the scan
  over a chunk's layer slots, which slices each slot's weights and stacks
  what the backward reads and the slots' gradients), ``mla.towers``
  inside ``attention``, the MoE scopes inside ``mlp``; ``stage_stack`` copies the weights into the executor's
  chunk-stacked layout at the step's start and the gradients out of it.

The names are fixed, whatever the configuration.  ``scope_map`` reads
them back from the compiled step's HLO text (``compiled.as_text()``),
whose instruction names are the op names a device trace shows.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

TICK_F = "tick.F"
TICK_B = "tick.B"
TICK_W = "tick.W"
TICKS = (TICK_F, TICK_B, TICK_W)

EMBED = "embed"              # token embedding (vocab-parallel under TP)
LAYER_SCAN = "layer_scan"    # the scan over slots: weight slices, stacking
ATTENTION = "attention"      # ln1, projections, flash or MLA, TP f/g
MLA_TOWERS = "mla.towers"    # MLA's query projection, KV latent, K/V, rope
MLP = "mlp"                  # ln2, the dense MLP, the FFN residual
MOE_ROUTE = "moe.route"      # router, aux, dispatch, all-to-alls, combine
MOE_EXPERTS = "moe.experts"  # the grouped expert FFN over the routed experts
MOE_SHARED = "moe.shared"    # the shared experts, every token
HEAD = "head"                # final norm, logits, cross-entropy
GRAD_ACCUM = "grad_accum"    # fp32 adds of each microbatch's gradient
GRAD_SYNC = "grad_sync"      # post-loop gradient psums, ZeRO reduce-scatter
OPTIMIZER = "optimizer"      # mean over microbatches, AdamW, ZeRO pins
STAGE_STACK = "stage_stack"  # weights into the stacked stage layout, grads out

MODEL_LAYERS = (EMBED, LAYER_SCAN, ATTENTION, MLA_TOWERS, MLP, MOE_ROUTE,
                MOE_EXPERTS, MOE_SHARED, HEAD)
LAYERS = MODEL_LAYERS + (GRAD_ACCUM, GRAD_SYNC, OPTIMIZER, STAGE_STACK)

FORWARD, REPLAY, BACKWARD = "forward", "replay", "backward"

# ``jvp(attention)``, ``transpose(jvp(attention))`` -> ``attention``
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def _core(component: str) -> str:
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def layer_of(path: str) -> Optional[str]:
    """The innermost layer scope of an ``op_name`` path, or None."""
    for comp in reversed(path.split("/")):
        core = _core(comp)
        if core in LAYERS:
            return core
    return None


def phase_of(path: str) -> Optional[str]:
    """``forward`` under ``tick.F``; under ``tick.B``, ``backward`` where
    the op is a transpose and ``replay`` where it is the forward that
    ``jax.vjp`` runs again; None elsewhere (``tick.W``, the gradient
    accumulation outside the vjp, the steps after the tick loop)."""
    comps = path.split("/")
    if TICK_F in comps:
        return FORWARD
    if TICK_B in comps:
        if "transpose(" in path:
            return BACKWARD
        if "jvp(" in path:
            return REPLAY
    return None


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CONTROL = ("while", "conditional", "call")


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Each instruction's name -> its ``op_name`` path, from an HLO
    module's text.  An instruction the compiler made carries no path, or
    the unscoped path of the loop or branch it sits in (a copy, a split
    dot, a rewritten scatter); it takes the path of an operand, the first
    that has a layer scope, else the first that has a path of its own,
    else keeps its own (or "")."""
    rows = []
    control = set()
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        found = _OP_NAME.search(rest)
        path = found.group(1) if found else ""
        if op and op.group(1) in _CONTROL:
            control.add(path)
        rows.append((name, path,
                     _OPERAND.findall(rest[op.end():]) if op else []))

    def usable(path: str) -> bool:
        return bool(path) and (path not in control
                               or layer_of(path) is not None)

    out: Dict[str, str] = {}
    for name, path, operands in rows:
        if not usable(path):
            paths = [out[o] for o in operands if usable(out.get(o, ""))]
            path = next((p for p in paths if layer_of(p)),
                        paths[0] if paths else path)
        out[name] = path
    return out
