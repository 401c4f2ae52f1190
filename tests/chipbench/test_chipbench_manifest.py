"""``BENCHMARK.json`` held to its own rules: the keys of each entry, names,
units and one-line texts, the paths and the command, the bounds and the
run length, and the tie between cells and metrics: every per-layer metric
has a reader, lists exactly the cells its reader applies to, and moves an
end-to-end metric that each of those cells reports."""

import json
import os
import re
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import pytest  # noqa: E402

from chipbench import manifest  # noqa: E402

PATH = os.path.join(REPO, "BENCHMARK.json")
with open(PATH) as _f:
    M = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
REL_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# keys that name a width, which a configuration may never cut
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|expan"
                   r"|_dim$|_rank$|experts_per_tok|num_experts_per)")
CELLS = [w["name"] for w in M["workloads"]]


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def _under_paths(rel):
    return any(rel == p or rel.startswith(p.rstrip("/") + "/")
               for p in M["paths"])


def test_top_level_keys_size_and_run_length():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(PATH) <= 64 * 1024
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits the check's time
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_paths_and_command():
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert REL_PATH.match(p) and not p.startswith("/")
        assert ".." not in p.split("/")
        assert os.path.isdir(os.path.join(REPO, p))
    cmd = M["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(c) for c in cmd)
    for word in cmd:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(REPO, word)):
            assert _under_paths(word), word


@pytest.mark.parametrize("entry", M["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert _line(entry["source"]) and _line(entry["why"])
    assert _under_paths(entry["file"])
    with open(os.path.join(REPO, entry["file"])) as f:
        assert json.load(f)["name"] == entry["name"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert any(w["config"] == entry["name"] for w in M["workloads"])


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda e: e["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for k in ("name", "config", "traffic"):
        assert NAME.match(cell[k]), cell[k]
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert cell["config"] in {c["name"] for c in M["configs"]}
    assert os.path.exists(os.path.join(REPO, "chipbench", "traffic",
                                       cell["traffic"] + ".json"))
    assert os.path.exists(os.path.join(REPO, "chipbench", "limits",
                                       cell["name"] + ".json"))
    loaded = manifest.load_cell(cell["name"])
    e2e = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loaded.per_layer


def test_names_are_unique_and_four_chip_cells_few():
    for group in (M["configs"], M["workloads"],
                  M["end_to_end"] + M["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda e: e["name"])
def test_end_to_end_entry(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_setup_time_is_reported_everywhere():
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["unit"] == "s"


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda e: e["name"])
def test_per_layer_entry_and_its_reader(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES and _line(metric["layer"])
    moves = next(m for m in M["end_to_end"] if m["name"] == metric["moves"])
    reader = manifest.layer_metric(metric["name"])
    assert reader.SOURCE == metric["source"] and callable(reader.read)
    applies = {c for c in CELLS
               if reader.applies(manifest.load_cell(c))}
    assert set(metric["workloads"]) == applies
    assert applies <= set(moves.get("workloads", CELLS))


def test_one_layer_name_per_layer():
    """Metrics of one module give its layer letter for letter."""
    by_module = {}
    for m in M["per_layer"]:
        module = m["layer"].split(" (")[0]
        by_module.setdefault(module, set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_module.values()), by_module
