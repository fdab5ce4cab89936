"""BENCHMARK.json against the contract's limits that can be checked
here, and every file it names."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    n4 = sum(w["chips"] == 4 for w in bench["workloads"])
    assert n4 <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_lines(bench):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
            for k in ("why", "layer"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k], (e["name"], k)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_named_file_resolves(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert not w["name"].startswith("toy_")
        wl_path = os.path.join(BENCH, "workloads", w["name"] + ".json")
        with open(wl_path) as f:
            wl = json.load(f)
        assert wl["config"] == w["config"] and wl["traffic"] == w["traffic"]
        assert wl["chips"] == w["chips"]
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           wl["driver"] + ".py"))
        used.add(w["config"])
    assert used == set(configs)
    for c in bench["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for kind in ("models", "reference"):
            assert os.path.exists(os.path.join(BENCH, kind,
                                               cfg["builder"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_per_layer_metrics_move_a_metric_their_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]], (m, cell)
    for cell in cells:   # setup_s + one more end to end, one per layer
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


CELL_1 = {
    "name": "resnet50.train.b256", "config": "resnet50_v1",
    "traffic": "train.b256", "chips": 1,
    "why": "batch 256 of 224x224 images, 4 resident batches back to back: "
           "conv + BatchNorm + SGD in one donated program; bypasses input "
           "pipeline, attention kernels and serving"}
LOADER = "resnet50.train.dataloader"


def test_cell_1_is_what_it_was(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["resnet50.train.b256"] == CELL_1
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert (bounds["train_samples_s"], bounds["setup_s"]) == (0.01, 0.1)


def test_the_loader_workload_is_issue_28s_traffic():
    """Measured and `correct` on the chip, not listed as a cell: a run
    keeps one of two paces of the loader's result pipe, some 10% apart,
    through a window of 50 s as of 10 (PERF.md section 7).  The file is
    the traffic to the letter."""
    with open(os.path.join(BENCH, "workloads", LOADER + ".json")) as f:
        wl = json.load(f)
    tp = wl["traffic_params"]
    assert (wl["config"], wl["driver"], wl["chips"]) == (
        "resnet50_v1", "train_loader", 1)
    assert tp["loader"] == {"batch_size": 256, "shuffle": True,
                            "last_batch": "discard", "num_workers": 4}
    assert (tp["pool_images"], tp["dataset_length"], tp["flip_p"]) == (
        5120, 1281167, 0.5)
    assert tp["mean"] == [0.485, 0.456, 0.406]
    assert tp["std"] == [0.229, 0.224, 0.225]
    for name in ("data_wait_ms.train", "data_wait_p95_ms.train"):
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
