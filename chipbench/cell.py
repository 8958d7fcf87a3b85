"""A cell of ``BENCHMARK.json`` and the data files it names.

Everything that belongs to one configuration, mix, accumulation setting,
cell or metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``   (the entry's ``file``) the model and its
  deployment;
- ``mixes/<traffic>.json``    the traffic mix;
- ``cells/<workload>.json``   the cell's accumulation setting (and,
  where set-up builds long contexts, the one it builds them under), its
  load and the limits of its correctness check;
- ``accum/<accum>.json``      the ``IntegerLinConfig`` fields;
- ``end_to_end/<metric>.py`` and ``metrics/<metric>.py``  one reader per
  metric, each with its ``UNIT`` and ``read(run) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    load: dict
    accum: dict
    end_to_end: tuple
    per_layer: tuple
    setup_accum: Optional[dict] = None


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench_file: Path = ROOT / "BENCHMARK.json"
            ) -> Cell:
    bench = _json(bench_file)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    load = _json(HERE / "cells" / f"{workload}.json")
    return Cell(
        name=workload, chips=w["chips"],
        config=_json(ROOT / cfg["file"]),
        mix=_json(HERE / "mixes" / f"{w['traffic']}.json"),
        load=load,
        accum=_json(HERE / "accum" / f"{load['accum']}.json"),
        end_to_end=tuple(m["name"] for m in bench["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(m["name"] for m in bench["per_layer"]
                        if _applies(m, workload)),
        setup_accum=(_json(HERE / "accum" / f"{load['setup_accum']}.json")
                     if "setup_accum" in load else None),
    )


def reader(kind: str, metric: str):
    """The module ``<kind>/<metric>.py``: its ``UNIT`` and ``read``."""
    path = HERE / kind / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{metric}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
