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


# ---------------------------------------------------- one entry a metric (PR 41)

#: per-layer entries each cell reports: the readers it had before the fold
#: (10, 17, 15, 10, 22, 24, 24, 26) and the entries PR 41 added
REPORTS = {"m7b.restore": 10, "m7b.flood": 17 + 1, "m7b.chat": 15,
           "m7b-tp4.restore4": 10, "g4hm.flood": 22 + 1, "lfm2.flood": 24 + 1,
           "k2c.flood8k": 24 + 4, "mimo.flood16k": 26 + 5}
#: a name's tag that stands for ONE configuration
TAGS = {"g4hm": "granite-4.0-h-micro", "lfm2": "lfm2-24b-a2b",
        "k2c": "kimi-k2.7-code", "mimo": "mimo-v2.5"}
ADDED = {"attn_grid_steps": {"m7b.flood", "g4hm.flood", "lfm2.flood",
                             "k2c.flood8k", "mimo.flood16k"},
         "moe_rounds_per_call": {"k2c.flood8k", "mimo.flood16k"},
         "moe_experts_roofline": {"k2c.flood8k", "mimo.flood16k"},
         "moe_prefill_experts_roofline": {"k2c.flood8k", "mimo.flood16k"},
         "full_attn_share": {"mimo.flood16k"}}


def _reader(m: dict) -> str:
    return m["name"].split(".", 1)[0]


def test_one_entry_for_each_reader_and_moved_metric():
    per = B["per_layer"]
    assert len(per) <= 128
    keys = [(_reader(m), m["moves"]) for m in per]
    assert len(keys) == len(set(keys)), [k for k in keys if keys.count(k) > 1]
    config_of = {w["name"]: w["config"] for w in B["workloads"]}
    for m in per:
        assert "workloads" in m and m["workloads"], m["name"]
        assert len(m["workloads"]) == len(set(m["workloads"]))
        tag = m["name"].split(".", 1)[1]
        if tag in TAGS:            # no cell reports under another's name
            assert {config_of[c] for c in m["workloads"]} == {TAGS[tag]}, m
        elif tag == "restore":
            assert m["moves"] == "data_gib_s" and m["workloads"] == [
                "m7b.restore", "m7b-tp4.restore4"]
        elif tag == "chat":
            assert m["moves"] == "ttft_p50_ms" \
                and m["workloads"] == ["m7b.chat"]
        else:
            assert tag == "flood" and m["moves"] == "tok_s", m


def test_every_cell_reads_what_it_read_before_the_fold():
    """The readers of each cell on the parent commit (its ``BENCHMARK.json``
    of 128 entries, written down here by reader), plus only what PR 41
    added and lists for the cell."""
    common = {"admit_share", "compiles_in_window", "decode_step_dev_ms",
              "device_idle", "hbm_peak_gib", "prefill_share",
              "prefill_pad_share", "idle_in_prefill", "idle_in_admit_rest",
              "step_host_ms_max"}
    flood = common | {"prefill_batch_mean", "step_unscoped_share",
                      "step_attn_share", "prefill_us_per_row"}
    scoped = {"step_head_share", "prefill_unscoped_share",
              "prefill_mixer_share"}
    shares = flood | scoped | {"step_mlp_share", "prefill_dev_share",
                               "moe_local_pair_share"}
    restore = {"restore_s_p50", "h2d_dispatch_share", "direct_share",
               "device_idle", "hbm_peak_gib", "plan_share", "read_wait_share",
               "slice_share", "retire_wait_share", "restore_self_share"}
    before = {
        "m7b.restore": restore, "m7b-tp4.restore4": restore,
        "m7b.flood": flood | {"decode_step_roofline", "step_mlp_share",
                              "step_staged_copy_share"},
        "m7b.chat": common | {"gen_late_p90_ms", "admit_wait_p50_ms",
                              "ttft_p90_ms", "tpot_mean_ms", "tpot_p50_ms"},
        "g4hm.flood": flood | scoped | {
            "step_mlp_share", "hybrid_step_roofline", "ssm_update_roofline",
            "ssm_scan_roofline", "ssm_step_share"},
        "lfm2.flood": flood | scoped | {
            "moe_step_roofline", "moe_experts_roofline",
            "moe_prefill_experts_roofline", "moe_experts_share",
            "moe_route_share", "moe_load_max_over_mean",
            "moe_tile_pad_share"},
        "k2c.flood8k": shares | {"mla_attn_roofline", "mla_attn_share",
                                 "mla_step_roofline", "prefill_mfu"},
        "mimo.flood16k": shares | {
            "swa_step_roofline", "swa_prefill_mfu", "full_attn_roofline",
            "window_attn_roofline", "kv_prefill_roofline",
            "window_attn_share"}}
    assert [len(before[w["name"]]) for w in B["workloads"]] == [
        10, 17, 15, 10, 22, 24, 24, 26]
    for w in B["workloads"]:
        cell = w["name"]
        _, per = run.cell_metrics(B, cell)
        readers = [_reader(m) for m in per]
        assert len(readers) == len(set(readers)) == REPORTS[cell], cell
        added = {r for r, cells in ADDED.items() if cell in cells}
        assert set(readers) == before[cell] | added, (
            cell, set(readers) ^ (before[cell] | added))
