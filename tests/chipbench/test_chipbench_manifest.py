"""BENCHMARK.json against the contract's limits, and every cell's files found by name."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|expansion|experts_per_tok")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


M = manifest()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = [m["name"] for m in M["end_to_end"] + M["per_layer"]]


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert len(json.dumps(M)) < 64 * 1024
    assert 1 <= len(M["paths"]) <= 16 and all(os.path.isdir(os.path.join(ROOT, p)) for p in M["paths"])
    assert all(not word.startswith("/") and ".." not in word for word in M["command"])
    # a full check of 24 cells has to fit the driver's 43200 s
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("name", sorted(set(METRICS + CELLS + [c["name"] for c in M["configs"]])))
def test_names_use_allowed_characters(name):
    assert NAME.match(name)


def test_no_name_twice():
    for group in (METRICS, CELLS, [c["name"] for c in M["configs"]]):
        assert len(group) == len(set(group))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in M["end_to_end"]:
        assert set(metric) <= allowed | {"bound"} and 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        moved = next(e for e in M["end_to_end"] if e["name"] == metric["moves"])
        for cell in metric.get("workloads", CELLS):
            assert cell in CELLS and ("workloads" not in moved or cell in moved["workloads"])
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve_by_name(cell):
    from chipbench import run

    assert set(cell) == {"name", "config", "traffic", "chips", "why"} and cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and NAME.match(cell["traffic"])
    _, config = run.find_cell(M, cell["name"])
    with open(os.path.join(ROOT, config["file"])) as f:
        stated = json.load(f)
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert sorted(config["reduced"]) == sorted(stated["reduced"]) and len(config["reduced"]) <= 16
    assert not any(WIDTH.search(k) for k in config["reduced"])
    with open(run.find_file(M, "traffic", cell["traffic"], (".json",))) as f:
        traffic = json.load(f)
    generator = run.load(M, "generators", traffic["generator"])
    assert callable(generator.run) and callable(run.load(M, "builders", stated["bench"]["builder"]).build)
    family = run.load(M, "reference", stated["bench"]["reference"])
    assert all(hasattr(family, name) for name in generator.FAMILY_GIVES), "the family gives what its cell's generator asks"
    assert cell["config"] in traffic["limits"], "a cell's limits are set from readings, in its traffic file"
    reported = [m for m in M["end_to_end"] if run.applies(m, cell["name"])]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    layers = [m for m in M["per_layer"] if run.applies(m, cell["name"])]
    assert layers
    for m in layers:
        assert callable(run.load(M, "layers", m["name"]).read)


def test_every_config_is_used_and_four_chip_cells_are_few():
    assert {c["name"] for c in M["configs"]} == {w["config"] for w in M["workloads"]}
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(M["workloads"]) // 4)
