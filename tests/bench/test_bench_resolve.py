"""Every cell, configuration, traffic mix, limit and metric that
BENCHMARK.json names resolves to its file, and the file holds what the
benchmark's contract asks of it."""

import json
import re

import jax
import pytest

from bench import arch, cells, check
from bench import weights as W

BM = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    for p in BM["paths"]:
        assert (cells.ROOT / p).is_dir()
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    cell = cells.resolve(w["name"])
    assert cell.chips in (1, 4)
    assert cell.chips == eval("*".join(map(str, cell.config["parallel"]
                                           ["mesh"])))
    assert cell.limits and set(cell.limits) <= set(check.NAMES)
    assert len(w["why"]) <= 200
    t = cell.traffic
    assert t["global_batch"] % t["n_micro"] == 0
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "tokens_per_s"} <= names
    assert cell.per_layer


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    path = cells.ROOT / c["file"]
    assert path.is_file() and path.parts[-2] == "configs"
    with open(path) as f:
        config = json.load(f)
    assert config["name"] == c["name"] and config["source"] == c["source"]
    assert sorted(config["reduced"]) == sorted(c["reduced"])
    # its description reads it, and lays out weights for it
    desc = arch.of(config)
    d = desc.dims_of(config)
    assert d.h > 0 and d.vocab > 0
    assert jax.tree.leaves(W.abstract(desc, d))
    assert any(w["config"] == c["name"] for w in BM["workloads"])


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_metric_reader(m):
    assert callable(cells.load_metric(m["name"]))
    assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
    for w in m.get("workloads", []):
        assert w in {c["name"] for c in BM["workloads"]}


def test_names_and_units():
    entries = BM["configs"] + BM["workloads"] + BM["end_to_end"] \
        + BM["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BM[group]]
        assert len(names) == len(set(names))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cells.resolve("no-such-cell")


def test_peaks_table():
    p = cells.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("cpu")
