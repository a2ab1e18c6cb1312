"""Every configuration, cell, metric and kernel is a file of its own that the
harness finds by the name ``BENCHMARK.json`` gives it, and names only what
exists."""
import json
import re

import pytest

from chipbench import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]


def test_names_and_keys():
    names = CELLS + CONFIGS + METRICS + [m["name"] for m in BENCH["end_to_end"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s",
                                                        "tokens_per_s"}
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_is_what_the_program_runs(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert entry["file"] == f"chipbench/configs/{name}.json"
    conf = harness.load_json("configs", name)
    assert conf["name"] == name and conf["source"] == entry["source"]
    assert set(entry["reduced"]) == set(conf["reduced"])
    harness.program_config(conf)  # raises on a size the program does not run


@pytest.mark.parametrize("name", CELLS)
def test_cell_file_names_a_known_config_and_limits(name):
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    cell = harness.load_json("cells", name)
    assert cell["name"] == name
    assert cell["config"] == entry["config"] and entry["config"] in CONFIGS
    assert cell["chips"] == entry["chips"]
    assert cell["runtime"] in ("actor", "table")
    assert set(cell["limits"]) == {"loss", "grad", "update"}


@pytest.mark.parametrize("name", METRICS)
def test_metric_has_a_reader_and_known_cells(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert callable(harness.load_module("metrics", name).read)
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    if name.endswith("_roofline"):
        k = harness.load_module("kernels", name[: -len("_roofline")])
        for fn in ("shapes", "flops", "bytes_moved", "match"):
            assert callable(getattr(k, fn))
