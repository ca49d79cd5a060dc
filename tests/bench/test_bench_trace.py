"""The reduction from a profiler trace to the per-layer metrics, on traces
recorded on one TPU v5e chip (three steps of ``olmoe.1l.s4096.m4``'s
window, and one scoped step with its scope map; ``bench/testdata``) and
on hand-made events."""

import gzip
import json

import pytest

from bench import cells
from bench import trace as T

RECORDED = cells.ROOT / "bench" / "testdata" / \
    "olmoe.1l.s4096.m4.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    return T.from_file(str(RECORDED))


def test_recorded_window(recorded):
    # one device; the three steps' ops lie inside the host's window span;
    # the device idles between steps and at the window's end, 0.42 %
    assert list(recorded.devices) == [0]
    assert 1.32 < recorded.window_s() < 1.34
    assert 0 < recorded.busy_s() <= recorded.window_s()
    assert 0.003 < recorded.idle_share(0) < 0.006


def test_recorded_kernels(recorded):
    # per step: flash once per microbatch forward and once in its
    # backward's replay (4 microbatches), the expert FFN's three grouped
    # matmuls as often
    flash = recorded.events(lambda n: n.startswith("_flash_attention_jit"))
    gmm = recorded.events(lambda n: n.startswith("_gmm_jit"))
    assert len(flash[0]) == 3 * 4 * 2
    assert len(gmm[0]) == 3 * 4 * 2 * 3


SCOPED = cells.ROOT / "bench" / "testdata" / \
    "olmoe.1l.s4096.m4.scoped.xplane.pb.gz"
SCOPED_MAP = cells.ROOT / "bench" / "testdata" / \
    "olmoe.1l.s4096.m4.scoped.scopes.json.gz"
# the recording keeps no counters: these are the last step's of a 23-step
# window of the same cell on the same chip (4 x 4096 tokens, top-8)
COUNTERS = {"moe_routed": 131072, "moe_kept": 56277}


def test_recorded_metrics():
    """Every per-layer metric of the cell, on one scoped step recorded on
    one TPU v5e chip, with its scope map, as a ``--trace 1`` run hands
    them the readers."""
    with gzip.open(SCOPED_MAP, "rt") as f:
        smap = json.load(f)
    scoped = T.from_file(str(SCOPED))
    cell = cells.resolve("olmoe.1l.s4096.m4")
    ctx = {"config": cell.config, "traffic": cell.traffic, "chips": 1,
           "steps": 1, "kind": "TPU v5 lite", "scopes": smap,
           "counters": COUNTERS}
    got = {m["name"]: cells.load_metric(m["name"])(scoped, ctx)
           for m in cell.per_layer}
    for name, v in got.items():
        assert v is not None and 0 < v <= 100, (name, v)
    # the step at 0.45 s: about a fifth of the bf16 peak
    assert 15 < got["step_mfu"] < 25
    # the static-capacity kernel can read at most 1 / capacity factor
    assert got["gmm_fwd_roofline"] <= 100 / 1.25
    # the scope shares, as read when the step was recorded: 20.46, 26.34,
    # 36.18 %
    assert 18 < got["attention.device_pct"] < 23
    assert 24 < got["head.device_pct"] < 29
    assert 34 < got["moe.experts_pct"] < 39
    assert got["moe.dropped_pct"] == 100 * (1 - 56277 / 131072)


def test_recorded_breakdown(recorded):
    b = recorded.breakdown()
    kinds = [n for n, _ in b["device_ops"]]
    assert "_gmm_jit" in kinds and "while" not in kinds
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    total = sum(v for _, v in b["device_ops"])
    assert total <= recorded.busy_s() * 1.0001


def test_op_names():
    text = "%_gmm_jit.71 = bf16[40960,2048]{1,0} custom-call(s32[320] %x)"
    assert T.op_name(text) == "_gmm_jit.71"
    assert T.op_kind("_gmm_jit.71") == "_gmm_jit"
    assert T.op_kind("fusion.625.clone") == "fusion"


def _trace(ops, host=()):
    return T.Trace({0: list(ops)}, [("window", 0, 100)] + list(host))


def test_busy_and_idle_union():
    tr = _trace([("a", 10, 30), ("b", 20, 40), ("c", 90, 120)])
    assert tr.busy_ns(0) == 30 + 10            # [10, 40] and [90, 100]
    assert abs(tr.idle_share(0) - 0.6) < 1e-12


def test_self_time_of_enclosing_ops():
    # an op that encloses others is no work of its own: only the leaves
    ev = [("while", 0, 100), ("k", 10, 30), ("f", 40, 50), ("g", 42, 48)]
    assert T.leaves(ev) == [("k", 10, 30), ("g", 42, 48)]
    # partial overlaps are siblings, not parents
    ev = [("a", 10, 30), ("b", 20, 40)]
    assert T.leaves(ev) == ev


def test_gap_inside_a_while_is_idle():
    tr = _trace([("while", 0, 100), ("k", 0, 40), ("f", 70, 100)])
    assert tr.busy_ns(0) == 70
    assert abs(tr.idle_share(0) - 0.3) < 1e-12
    assert tr.breakdown()["idle_gaps"] == [["window", 30 / 1e9]]


def test_collective_alone():
    coll = lambda n: n.startswith("all-")
    tr = _trace([("fusion", 0, 30), ("all-reduce", 20, 50),
                 ("all-to-all", 60, 70), ("fusion", 65, 80)])
    # [30, 50] and [60, 65]
    assert tr.alone_ns(0, coll) == 25


def test_idle_gaps_named_by_host_span():
    tr = _trace([("a", 0, 40), ("b", 70, 100)],
                host=[("dispatch", 35, 60)])
    gaps = tr.breakdown()["idle_gaps"]
    assert gaps == [["dispatch", 30 / 1e9]]


def test_collectives_metric_reads_shard_map_names():
    # the names a four-chip trace of the executor shows: psum and
    # all_to_all from shard_map, all-gather from XLA
    tr = _trace([("fusion.1", 0, 20), ("psum.3", 20, 30),
                 ("all_to_all.2", 40, 50), ("all-gather.7", 60, 70),
                 ("fusion.2", 65, 90)])
    got = cells.load_metric("collectives.exposed_pct")(tr, {})
    assert abs(got - 25.0) < 1e-9               # 10 + 10 + 5 of 100
    assert cells.load_metric("collectives.exposed_pct")(
        _trace([("fusion.1", 0, 20)]), {}) is None
