"""``BENCHMARK.json`` and the files it names: every name resolves to a
file, and every name and unit keeps to the characters allowed."""
import json
import os
import re

import pytest

from chipbench import harness, reference

HERE = harness.HERE
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def test_top_level_keys(bench):
    assert set(bench) == TOP_KEYS
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_and_units(bench):
    names = [c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(UNIT.match(u) for u in units), units
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_each_config_and_traffic_pair_is_one_cell(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs), pairs


@pytest.mark.parametrize("workload", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_cell_files_resolve(bench, workload):
    cell = harness.load_cell(bench, workload)
    cfg = reference.load_config(cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    assert os.path.exists(os.path.join(HERE, "entries", cell["entry"] + ".py"))
    assert os.path.exists(os.path.join(HERE, "loops", traffic["loop"] + ".py"))
    assert cell["chips"] in (1, 4)
    assert cell.get("m", cell["chips"]) <= cell["chips"]
    assert 0 < cell["limits"]["max_rel_err"] < 1
    assert cfg["matmul_precision"] == "highest"


def test_every_metric_has_a_reader_and_every_cell_reports(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics", m["name"] + ".py")), m["name"]
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for w in bench["workloads"]:
        assert "setup_s" in {m["name"] for m in harness.metrics_for(bench, w["name"], False)}
        assert harness.metrics_for(bench, w["name"], True)


def test_configs_are_used_and_their_files_exist(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_peaks_name_their_source():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    assert peaks["TPU v5 lite"]["flops_per_s"] == 1.97e14
    assert all(p["source"] for p in peaks.values())
    with pytest.raises(KeyError):
        harness.peak_flops("cpu")
