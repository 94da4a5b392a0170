"""``BENCHMARK.json`` against the contract's limits that can be checked
without a chip, and every name it gives against the files that must exist."""

import os
import re

from benchmark import harness, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = harness.load_json("BENCHMARK.json")


def test_keys_names_units():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert any(m["name"] == "setup_s" for m in B["end_to_end"])
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) <= 64 << 10


def test_cells_configs_and_files():
    cells = [w["name"] for w in B["workloads"]]
    assert len(cells) == len(set(cells)) and 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(cells) // 4)
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = harness.load_json(c["file"])
        assert c["file"].startswith("benchmark/")
        assert set(c["reduced"]) == set(cfg["reduced"])
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate)", key)
        harness.plugin("reference", cfg["reference"])
        assert os.path.exists(os.path.join(
            harness.ROOT, c["file"].replace(".json", ".reference.py")))
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        traffic = harness.load_json("benchmark", "traffic",
                                    w["traffic"] + ".json")
        harness.plugin("traffic.kinds", traffic["kind"])
        harness.plugin("runners", traffic["runner"])


def test_every_metric_has_a_reader_and_every_cell_reports_enough():
    e2e_names = {m["name"] for m in B["end_to_end"]}
    for w in B["workloads"]:
        e2e, per = run.cell_metrics(B, w["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert len(per) >= 1
        for m in e2e:
            assert callable(harness.plugin("end_to_end", m["name"]).read)
        for m in per:
            assert callable(harness.plugin("layer_metrics", m["name"]).read)
            assert m["moves"] in {x["name"] for x in e2e}, (w["name"], m)
    for m in B["per_layer"]:
        assert m["moves"] in e2e_names
    layers = {m["layer"] for m in B["per_layer"]}
    perf = open(os.path.join(harness.ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, layer
