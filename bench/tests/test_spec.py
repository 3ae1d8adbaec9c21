"""``BENCHMARK.json`` and the files it names hold together."""
import json
import re

import pytest

from conftest import BENCH
from harness import spec

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCHMARK["end_to_end"]}
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_top_level_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    n = 24   # the check must fit with the full 24 cells
    assert (2 + 14 * n) * (BENCHMARK["run_seconds"] + 60) \
        + n * 2 * 90 + 1200 <= 43200


def test_names_and_units():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCHMARK[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = spec.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(c.reader(m["name"]).read)
    for m in c.per_layer:
        assert m["moves"] in e2e
    assert c.cell["rate_rps"] > 0
    assert 0 < c.cell["check"]["max_logit_gap"]


def test_configs():
    used = {w["config"] for w in BENCHMARK["workloads"]}
    for c in BENCHMARK["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((BENCH.parent / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert set(cfg["reduced"]) <= set(cfg["published"])
        # the CPU tests' sizes change model fields only
        assert set(cfg["tiny"]) <= set(cfg["model"])
        # the cells of one configuration share its correctness limit
        limits = {json.loads((BENCH / "cells" / f"{w['name']}.json")
                             .read_text())["check"]["max_logit_gap"]
                  for w in BENCHMARK["workloads"]
                  if w["config"] == c["name"]}
        assert len(limits) == 1


def test_unknown_device_kind_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks_for("TPU v9 imaginary")
