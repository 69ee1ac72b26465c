"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix, limit and metric reader is there and parses."""
import dataclasses
import json

import pytest

from bench.context import METRICS_DIR
from bench.run import load_cell
from bench_tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads(cell):
    c = load_cell(cell)
    assert c.chips in (1, 4)
    assert {"tokens_per_s", "setup_s"} <= {m["name"] for m in c.end_to_end}
    assert c.per_layer
    checkmate = c.traffic["checkpointer"] == "checkmate"
    expect = {"grad_norm", "grad_err", "change_norm"}
    if checkmate:
        expect |= {"shadow_gap", "restore_gap"}
        assert "resume_s" in {m["name"] for m in c.end_to_end}
    assert set(c.limits) == expect
    for name, lim in c.limits.items():
        assert lim["limit"] > 0, name


def test_every_metric_names_existing_cells_and_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= set(CELLS), m["name"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (METRICS_DIR / f"{m['name']}.py").is_file(), m["name"]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_states_the_published_model_and_its_cuts(entry):
    """Every key of the file that the system's configuration has equals the
    published value there, except the keys in ``reduced``, which differ."""
    import repro.configs as C
    from repro.configs.base import ModelConfig
    model = json.loads((ROOT / entry["file"]).read_text())
    assert model["name"] == entry["name"]
    assert model["reduced"] == entry["reduced"]
    published = C.get(entry["name"])
    for f in dataclasses.fields(ModelConfig):
        if f.name in model and f.name != "name":
            if f.name in entry["reduced"]:
                assert model[f.name] != getattr(published, f.name)
                assert model["published"][f.name] == getattr(published,
                                                             f.name)
            else:
                assert model[f.name] == getattr(published, f.name), f.name


def test_traffic_files_parse():
    for w in BENCH["workloads"]:
        t = json.loads((ROOT / "bench" / "traffic" /
                        f"{w['traffic']}.json").read_text())
        assert t["batch"] % load_cell(w["name"]).model["microbatches"] == 0
        assert t["checkpointer"] in ("checkmate", "none")
