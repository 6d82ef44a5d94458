"""``BENCHMARK.json`` says what the data files say, inside the contract's
limits."""
import json
import os
import re

import pytest

import bm_util
from benchmark import harness

with open(os.path.join(bm_util.REPO, "BENCHMARK.json")) as f:
    B = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in B["workloads"]]


def test_keys_command_and_paths():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "-m", "benchmark.run"]
    assert B["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(bm_util.REPO, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_configuration_entries_match_their_files(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    data = harness.load_json(bm_util.REPO, cfg["file"])
    assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
    assert data["source"] == cfg["source"] and data["reduced"] == cfg["reduced"] == []
    for key in ("published", "model", "departures", "assumed", "precision",
                "reference", "control", "entry"):
        assert key in data, key
    assert any(w["config"] == cfg["name"] for w in B["workloads"])
    # no width is changed from the published file
    pub, m = data["published"], data["model"]
    width = lambda *keys: next(pub[k] for k in keys if k in pub)
    assert m["hidden_size"] == width("n_embd", "hidden_size")
    assert m["num_heads"] == width("n_head", "num_attention_heads")
    assert m["num_layers"] == width("n_layer", "num_hidden_layers")
    assert m["filter_size"] == width("n_inner", "ffn_dim")
    assert m["vocab_size"] == pub["vocab_size"]
    assert m["max_len"] == width("n_positions", "max_position_embeddings")


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cell_entries_match_their_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    data = harness.load_cell(cell["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert data[key] == cell[key]
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    assert data["config_data"]["entry"]["kind"] == data["traffic_data"]["kind"]
    reports = [m["name"] for m in B["end_to_end"]
               if cell["name"] in m.get("workloads", CELLS)]
    assert sorted(reports) == sorted(data["end_to_end"] + ["setup_s"])
    assert len(reports) >= 2 and harness.metrics_for(cell["name"])
    assert set(data["limits"]) and all(
        0 <= v < 1 for v in data["limits"].values()), "limits come from readings"


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(B["workloads"]) // 4)
    assert len({(w["config"], w["traffic"]) for w in B["workloads"]}) == len(CELLS)


@pytest.mark.parametrize("m", B["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metrics(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("lower", "higher")
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    listed = {x["name"]: x for x in
              harness.load_json(harness.HERE, "end_to_end.json")}
    assert listed[m["name"]]["unit"] == m["unit"]
    assert listed[m["name"]]["better"] == m["better"]


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_match_their_files(m):
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    data = harness.load_json(harness.HERE, "metrics", m["name"] + ".json")
    assert {k: data[k] for k in m} == m
    assert os.path.exists(os.path.join(harness.HERE, "metrics", data["reader"]))
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    moved = next(e for e in B["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
    assert NAME.match(m["name"]) and "\n" not in m["layer"] and len(m["layer"]) <= 200
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%" and m["better"] == "higher"


def test_every_metric_file_is_listed_and_every_step_mfu_stands_beside_a_roofline():
    files = {f[:-5] for f in os.listdir(os.path.join(harness.HERE, "metrics"))
             if f.endswith(".json")}
    assert files == {m["name"] for m in B["per_layer"]}
    for roof in (m for m in B["per_layer"] if m["name"].endswith("_roofline")):
        for cell in roof["workloads"]:
            assert any("mfu" in m["name"] and m["moves"] == roof["moves"]
                       and cell in m["workloads"] for m in B["per_layer"])


def test_no_reader_cell_or_mix_lies_unused():
    """Whatever sits under ``benchmark/`` is run by a cell of record: every
    reader is named by a metric file, every cell file is listed, every
    mix and configuration belongs to a listed cell."""
    here = lambda *p: os.listdir(os.path.join(harness.HERE, *p))
    readers = {f for f in here("metrics") if f.endswith(".py")}
    named = {harness.load_json(harness.HERE, "metrics", f)["reader"]
             for f in here("metrics") if f.endswith(".json")}
    assert readers == named
    assert {f[:-5] for f in here("workloads")} == set(CELLS)
    assert {f[:-5] for f in here("traffic")} == {w["traffic"] for w in B["workloads"]}
    assert {f[:-5] for f in here("configs")} == {c["name"] for c in B["configs"]}
    drivers = {harness.load_json(bm_util.REPO, c["file"])["entry"]["kind"]
               for c in B["configs"]}
    assert all(os.path.exists(os.path.join(harness.HERE, k + ".py"))
               for k in drivers)


@pytest.mark.parametrize("cell", CELLS)
def test_every_limit_names_a_number_the_run_compares(cell):
    data = harness.load_cell(cell)
    want = {f"loss{i}_gap" for i in range(1, data["traffic_data"]["check_steps"] + 1)}
    want |= {"grad_norm_gap", "delta_norm_gap"}
    if data["traffic_data"].get("grad_diff_leaves"):
        want.add("grad_diff")
    assert set(data["limits"]) == want
