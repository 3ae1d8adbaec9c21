"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by name:

* ``configs/<config>.json``: the model as it is run (program field names
  under ``model``), its published source, ``reduced``, ``assumed``, the
  deployment (workers, slots, cache) it stands for, and ``tiny``: the
  ``model`` fields the CPU tests (``tests/``) change to run it small;
* ``configs/<config>.py``: that configuration's plain float32 reference
  (``init`` makes the weights from the seed, ``forward`` gives logits),
  and the counts of its architecture where ``harness/counts.py``'s dense
  ones do not hold, each optional (``counts.Counts``):

  - ``prefill_flops(m, prompt_len)``: forward FLOPs of one prefill of the
    true prompt, one logits row;
  - ``decode_flops(m, attended)``: forward FLOPs of one decoded token that
    attends ``attended`` positions, its own included;
  - ``decode_least_bytes(m, attended, counters)``: least HBM bytes of one
    batched decode tick over live slots that attend ``attended``
    positions each; ``counters`` is the change of every program counter
    over that tick (``adapter.System.counters``: the harness's ten and
    each series of the program's registry as ``family{label=value}``), so
    that a count only the program knows (say, the experts that computed a
    token) can enter.  Where it is defined, the traced run reads the
    counters at every tick of its slice.

  ``m`` is the configuration's ``model`` merged with its ``reference``;
* ``traffic/<mix>.json``: the parameters the one generator reads;
* ``cells/<workload>.json``: the cell's offered rate and its limits;
* ``metrics/<metric>.py``: a reader ``read(record) -> float | None``
  (``cellrun._record`` says what a record holds: among others the
  counters at the middle and end of the window, and in a traced run at
  the slice's two ends, and the device seconds of each program and of
  each XLA op in the slice);
* ``peaks.json``: the device peaks, keyed by ``device_kind``.

A later cell, configuration, mix or metric is added as new files and new
``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file by path (config references and metric readers have
    names, such as ``deepseek-coder-33b``, that are no Python identifiers)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names."""
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<mix>.json
    cell: dict            # cells/<workload>.json
    reference: object     # configs/<config>.py, imported
    end_to_end: list      # this cell's end-to-end metric entries
    per_layer: list       # this cell's per-layer metric entries
    bench_dir: Path

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           "metric_" + metric.replace("-", "_")
                           .replace(".", "_"))


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, *, bench: dict | None = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named ``workload``.  ``bench`` is the parsed
    ``BENCHMARK.json`` (read from the checkout's root when not given);
    ``bench_dir`` holds the configs, traffic, cells and metrics (tests point
    it at small ones)."""
    if bench is None:
        bench = _json(ROOT / "BENCHMARK.json")
    match = [w for w in bench["workloads"] if w["name"] == workload]
    if not match:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = match[0]
    cfg_path = bench_dir / "configs" / f"{w['config']}.json"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(cfg_path),
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        cell=_json(bench_dir / "cells" / f"{workload}.json"),
        reference=load_module(cfg_path.with_suffix(".py"),
                              "ref_" + w["config"].replace("-", "_")
                              .replace(".", "_")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir)


def peaks_for(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = _json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
