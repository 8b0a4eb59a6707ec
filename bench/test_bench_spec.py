"""BENCHMARK.json against the benchmark's contract, and the rule that a
cell's files are found by name alone."""
import json
import re

import pytest

from bench import harness as H

SPEC = H.load_json(H.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert 1 <= SPEC["run_seconds"] <= 51
    n = 24               # the most cells later PRs may reach
    assert (2 + 14 * n) * (SPEC["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for k in ("configs", "workloads") for x in SPEC[k]]
    names += [m["name"] for k in ("end_to_end", "per_layer")
              for m in SPEC[k]]
    assert len(names) == len(set(names))


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    c = H.find_cell(cell, SPEC)
    assert c.config["name"] == next(w["config"] for w in SPEC["workloads"]
                                    if w["name"] == cell)
    H.driver_module(c.config["driver"])
    e2e = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = c.per_layer()
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e
        assert callable(H.metric_reader(m["name"]))


def test_configs_state_source_guarantee_and_cuts():
    for c in SPEC["configs"]:
        f = H.load_json(H.ROOT / c["file"])
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
        assert f["guarantee"] and isinstance(f["assumed"], dict)


def test_a_metric_without_a_cell_list_goes_to_every_cell_of_its_metric():
    spec = json.loads(json.dumps(SPEC))
    serve = next(w for w in spec["workloads"]
                 if w["name"] == "serve-qwen3-1.7b-over")
    spec["workloads"].append(dict(serve, name="x-cell"))
    spec["per_layer"].append({"name": "x.new", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "gen_tok_s"})
    got = {w["name"]: [m["name"] for m in H.find_cell(w["name"], spec)
                       .per_layer()] for w in spec["workloads"]}
    # gen_tok_s lists the serve cell only, so x-cell does not report it
    assert all(("x.new" in v) == (k == "serve-qwen3-1.7b-over")
               for k, v in got.items())
