"""``BENCHMARK.json`` keeps to the benchmark's rules, and the harness finds
every cell's configuration, traffic and metric files by name."""

import json
import math
import re

import pytest

from chipbench import loadgen, run

MAN = run.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def _line(text, limit=200):
    return isinstance(text, str) and 1 <= len(text) <= limit and \
        "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(MAN)) < 64 * 1024
    assert MAN["command"] == ["python3", "chipbench/run.py"]
    assert MAN["paths"] == ["chipbench"]
    assert isinstance(MAN["run_seconds"], int) and \
        1 <= MAN["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert _line(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in entry.get("reduced", []):
        assert NAME.match(key)


def test_names_are_unique():
    for group in (MAN["configs"], MAN["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_entries_have_just_their_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}
    assert "setup_s" in {e["name"] for e in MAN["end_to_end"]}


def test_every_config_is_used_and_its_file_is_its_own():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"


@pytest.mark.parametrize("cell", CELLS)
def test_harness_finds_the_cells_files(cell):
    parts = run.cell_parts(MAN, cell)
    cfg = parts["config"]
    assert cfg["name"] == parts["cell"]["config"]
    assert cfg["pipeline"]["dim"] == cfg["dim"]
    assert cfg["dim"] % cfg["pipeline"]["pq_m"] == 0
    assert isinstance(parts["mix"], loadgen.Mix)
    names = {m["name"] for m in parts["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert parts["per_layer"]
    for m in parts["per_layer"]:
        assert callable(run.load_metric(m["name"]).read)


def test_every_per_layer_metric_has_a_reader_and_cells():
    for m in MAN["per_layer"]:
        assert (run.BENCH / "metrics" / f"{m['name']}.py").exists()
        assert set(m["workloads"]) <= set(CELLS)


def test_peaks_are_keyed_by_device_kind():
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks("TPU v99")


def test_four_chip_cells_stay_within_half():
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, math.floor(len(CELLS) / 2))
