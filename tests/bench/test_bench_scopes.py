"""The reduction from a traced window to device time per layer scope and
phase (``bench/scopes.py``) and the metrics that read it: on a
hand-written HLO snippet, on hand-made events, and on one scoped step of
``olmoe.1l.s4096.m4`` recorded on a TPU v5e chip (``bench/testdata``)."""

import gzip
import json

import pytest

from bench import cells
from bench import scopes as BS
from bench import trace as T
from repro import scopes as S

TICK = "jit(step)/while/body/closed_call/cond/branch_1_fun/"
F_ATTN = TICK + "tick.F/layer_scan/while/body/closed_call/attention/dot"
R_ATTN = TICK + "tick.B/jvp(layer_scan)/while/body/closed_call/attention/dot"
B_ATTN = (TICK + "tick.B/transpose(jvp(layer_scan))/while/body/closed_call"
          "/attention/transpose(jvp())/dot")
R_HEAD = TICK + "tick.B/jvp(head)/dot_general"
B_ACC = TICK + "tick.B/grad_accum/add"

HLO = """\
HloModule jit_step, is_scheduled=true

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.3 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%f.1, \
metadata={op_name="@R_ATTN@"}
  %copy.4 = f32[8]{0} copy(%fusion.3)
  %fusion.5 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%f.2, \
metadata={op_name="jit(step)/while"}
  ROOT %tuple.6 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.5)
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %_gmm_jit.7 = f32[8]{0} custom-call(%a), \
custom_call_target="tpu_custom_call", \
metadata={op_name="@F_ATTN@" source_file="x.py" source_line=1}
  %while.8 = (s32[], f32[8]{0}) while(%t), condition=%c.1, body=%body.1, \
metadata={op_name="jit(step)/while"}
  ROOT %fusion.9 = f32[8]{0} fusion(%_gmm_jit.7), kind=kLoop, calls=%f.3
}
""".replace("@R_ATTN@", R_ATTN).replace("@F_ATTN@", F_ATTN)


def test_scope_map_on_hlo_text():
    smap = S.scope_map(HLO)
    assert smap["_gmm_jit.7"] == F_ATTN
    assert smap["fusion.3"] == R_ATTN
    # a copy the compiler made, and an op that carries only its loop's
    # path, take their operand's path
    assert smap["copy.4"] == R_ATTN and smap["fusion.5"] == R_ATTN
    assert smap["while.8"] == "jit(step)/while"
    assert smap["fusion.9"] == F_ATTN
    assert smap["a"] == ""


@pytest.mark.parametrize("path, layer, phase", [
    (F_ATTN, S.ATTENTION, S.FORWARD),
    (R_ATTN, S.ATTENTION, S.REPLAY),
    (B_ATTN, S.ATTENTION, S.BACKWARD),
    (R_HEAD, S.HEAD, S.REPLAY),
    (B_ACC, S.GRAD_ACCUM, None),
    ("jit(step)/optimizer/mul", S.OPTIMIZER, None),
    (TICK + "tick.F/layer_scan/while/body/dynamic_slice", S.LAYER_SCAN,
     S.FORWARD),
    (TICK + "tick.F/layer_scan/while/body/closed_call/mlp/moe.route/"
     "all_to_all", S.MOE_ROUTE, S.FORWARD),
    ("jit(step)/shard_map/psum", None, None),
], ids=["fwd", "replay", "bwd", "head-replay", "accum", "opt", "scan",
        "moe-in-mlp", "none"])
def test_layer_and_phase(path, layer, phase):
    assert S.layer_of(path) == layer
    assert S.phase_of(path) == phase


SMAP = {"fusion.1": F_ATTN, "fusion.2": R_ATTN, "fusion.3": B_ATTN,
        "fusion.4": R_HEAD, "fusion.5": "jit(step)/optimizer/mul",
        "fusion.6": B_ACC, "all-reduce.7": "jit(step)/grad_sync/psum",
        "all-to-all.8": TICK + "tick.F/layer_scan/while/body/closed_call/"
                        "mlp/moe.route/all_to_all",
        "_gmm_jit.9": TICK + "tick.F/layer_scan/while/body/closed_call/"
                      "mlp/moe.experts/jit(_gmm_jit)/pallas_call",
        "fusion.10": ""}


def _trace():
    # two devices over a 1000 ns window
    d0 = [("fusion.1", 0, 100), ("fusion.2", 100, 180),
          ("fusion.3", 180, 380), ("fusion.4", 380, 430),
          ("fusion.5", 450, 480), ("fusion.6", 480, 500),
          ("all-reduce.7", 500, 600), ("fusion.10", 590, 620),
          ("all-to-all.8", 620, 660), ("_gmm_jit.9", 660, 700)]
    d1 = [("fusion.2", 0, 300), ("all-reduce.7", 400, 450)]
    return T.Trace({0: d0, 1: d1}, [("window", 0, 1000),
                                    ("block", 700, 1000)])


def _read(name, ctx):
    return cells.load_metric(name)(_trace(), ctx)


CTX = {"scopes": SMAP,
       "counters": {"moe_routed": 1000, "moe_kept": 900}}


@pytest.mark.parametrize("name, want", [
    ("step.replay_pct", 30.0),          # device 1: fusion.2 for 300 ns
    ("attention.device_pct", 38.0),     # device 0: 100 + 80 + 200
    ("head.device_pct", 5.0),
    ("optimizer.device_pct", 5.0),      # optimizer 30 + grad_accum 20
    ("moe.route_pct", 4.0),
    ("moe.experts_pct", 4.0),
    ("grad_sync.exposed_pct", 9.0),     # device 0: 500-590 alone
    ("moe.dropped_pct", 10.0),
])
def test_readers_on_hand_made_events(name, want):
    assert _read(name, CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", BS.METRICS)
def test_readers_read_nothing_without_the_program_data(name):
    # a ctx with no scope map and no counters
    ctx = {"config": {}, "traffic": {}, "chips": 1, "steps": 1,
           "kind": "TPU v5 lite"}
    assert _read(name, ctx) is None


def test_moe_readers_silent_for_a_dense_step():
    dense = {n: p for n, p in SMAP.items() if "moe" not in p}
    ctx = {"scopes": dense, "counters": {"moe_routed": 0, "moe_kept": 0}}
    for name in ("moe.route_pct", "moe.experts_pct", "moe.dropped_pct"):
        assert _read(name, ctx) is None


def test_table_on_hand_made_events():
    tab = BS.table(_trace(), SMAP)
    sec = tab["seconds"]
    # seconds averaged over the two devices
    assert sec[S.ATTENTION][S.REPLAY] == pytest.approx((80 + 300) / 2e9)
    assert sec[S.ATTENTION][S.FORWARD] == pytest.approx(100 / 2e9)
    assert sec[BS.UNSCOPED][BS.NO_PHASE] == pytest.approx(30 / 2e9)
    busy = 700 - 20 + 300 + 50
    scoped = busy - 20    # fusion.10's 30 ns, 10 of them under all-reduce.7
    assert tab["scoped_pct"] == pytest.approx(100 * scoped / busy)
    assert tab["ops_mapped"] == [10, 10]


def test_named_gaps():
    tr = _trace()
    host = [("window", 0, 1000), ("block", 650, 1000),
            ("Wait for donation holds", 705, 990), ("dispatch", 430, 450)]
    # device 0 idles 430-450 and 700-1000; an event that opens after a
    # gap began does not name it
    assert BS.named_gaps(tr, host) == [["block", 300e-9],
                                       ["dispatch", 20e-9]]
    # the innermost event open at the gap's start names it, and the map
    # the op before it
    host.append(("Wait for donation holds", 690, 995))
    assert BS.named_gaps(tr, host, SMAP) == [
        ["Wait for donation holds", 300e-9, "moe.experts forward"],
        ["dispatch", 20e-9, "head replay"]]


RECORDED = cells.ROOT / "bench" / "testdata" / \
    "olmoe.1l.s4096.m4.scoped.xplane.pb.gz"
RECORDED_MAP = cells.ROOT / "bench" / "testdata" / \
    "olmoe.1l.s4096.m4.scoped.scopes.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED_MAP, "rt") as f:
        smap = json.load(f)
    return T.from_file(str(RECORDED)), smap


def test_recorded_scoped_step(recorded):
    tr, smap = recorded
    tab = BS.table(tr, smap)
    # every op of the step is in the map, the (layer, phase) times sum to
    # no more than busy time, and the scopes hold more than 95 % of it
    assert tab["ops_mapped"][0] == tab["ops_mapped"][1] > 0
    total = sum(v for ph in tab["seconds"].values() for v in ph.values())
    assert total <= tr.busy_s() * (1 + 1e-9)
    assert tab["scoped_pct"] > 95
    for layer in (S.ATTENTION, S.MOE_EXPERTS, S.HEAD):
        assert set(tab["seconds"][layer]) >= {S.FORWARD, S.REPLAY,
                                              S.BACKWARD}


def test_recorded_replay_share(recorded):
    # PERF.md records 25.75-26.53 % for olmoe.1l.s4096.m4 on one v5e chip
    tr, smap = recorded
    got = cells.load_metric("step.replay_pct")(tr, {"scopes": smap})
    assert 25.75 <= got <= 26.53


def test_run_scoped_on_the_cpu(tiny, tmp_path):
    """The script's path at a small size: the step's counters reach the
    result (one MoE layer, top-2: every token of the step routed twice;
    one fused forward per microbatch) and ``--record`` writes the trace
    and its map.  The CPU's trace has no
    TPU plane, so nothing is read from it."""
    import jax
    cell = tiny("moe")
    out = BS.run_scoped(cell, 2**31 + 12345, 0.2, jax.devices(),
                        record=str(tmp_path))
    tr = cell.traffic
    assert out["counters"]["moe_routed"] == \
        int(tr["global_batch"]) * int(tr["seq_len"]) * 2
    assert 0 < out["counters"]["moe_kept"] <= out["counters"]["moe_routed"]
    # at pp = 1 the backward tick runs every microbatch's only forward
    assert out["counters"]["fwd_fused"] == int(tr["n_micro"])
    assert out["metrics"]["step.replay_pct"] is None
    assert out["metrics"]["moe.dropped_pct"] == pytest.approx(
        100 * (1 - out["counters"]["moe_kept"]
               / out["counters"]["moe_routed"]))
    names = sorted(p.name for p in tmp_path.iterdir())
    assert names == [f"{cell.name}.scoped.scopes.json.gz",
                     f"{cell.name}.scoped.xplane.pb.gz"]
