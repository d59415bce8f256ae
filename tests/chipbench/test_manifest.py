"""``BENCHMARK.json`` against the contract it is written to, and the
files its names lead to."""

import os
import re

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["command"][:2] == ["python3", "chipbench/run.py"]
    assert manifest["paths"] == ["chipbench", "tests/chipbench"]
    size = os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_and_units_are_within_the_allowed_characters(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), n
    for w in manifest["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in manifest["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    for cell in cells:
        e2e = [m["name"] for m in manifest["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in m["workloads"] for m in manifest["per_layer"])
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def test_every_moves_target_is_reported_by_every_listed_cell(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)


def test_every_name_leads_to_its_file(manifest):
    used = set()
    for c in manifest["configs"]:
        path = os.path.join(harness.ROOT, c["file"])
        assert os.path.exists(path), c["file"]
        assert c["file"].startswith("chipbench/")
        cfg = harness.load_json(path)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["name"] == c["name"]
        assert os.path.exists(os.path.join(
            harness.HERE, "systems", f"{cfg['system']}.py"))
    for w in manifest["workloads"]:
        cell = harness.Cell(w["name"], manifest)
        used.add(w["config"])
        gen = cell.traffic["generator"]
        assert os.path.exists(os.path.join(harness.HERE, "generators",
                                           f"{gen}.py"))
        assert cell.limits, f"no limits for {w['name']}"
        for lim in cell.limits.values():
            assert "limit" in lim
    assert used == {c["name"] for c in manifest["configs"]}
    for m in manifest["per_layer"]:
        path = harness.reader_path(m["name"])
        assert os.path.exists(path), m["name"]
        assert hasattr(harness.load_module(path, "m"), "read")


def test_configurations_state_their_source_and_departures():
    lm = harness.load_json(harness.HERE + "/configs/cerebras-gpt-1.3b-standin.json")
    assert (lm["n_embd"], lm["n_layer"], lm["n_head"], lm["n_inner"],
            lm["vocab_size"], lm["n_positions"]) == \
        (2048, 24, 16, 8192, 50257, 2048)
    assert lm["reduced"] == [] and len(lm["departures"]) >= 3
    assert "huggingface.co/cerebras/Cerebras-GPT-1.3B" in lm["source"]
    # a stand-in says so in its names and says what it stands in for
    assert lm["serving"]["pallas"] == "off"
    assert lm["name"].endswith("-standin") and lm["stands_in_for"]
    rn = harness.load_json(harness.HERE + "/configs/resnet-50.json")
    assert (rn["batch_size"], rn["image_size"], rn["classes"]) == \
        (256, 224, 1000)
    assert rn["reduced"] == [] and "1512.03385" in rn["source"]
    assert (rn["depth"], rn["stage_blocks"], rn["stage_widths"]) == \
        (50, [3, 4, 6, 3], [64, 128, 256, 512])


def test_peaks_table_names_its_source_and_refuses_unknown_kinds():
    p = harness.peaks_for("TPU v5 lite")
    assert p["flops_per_s"]["bf16"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(SystemExit):
        harness.peaks_for("cpu")
