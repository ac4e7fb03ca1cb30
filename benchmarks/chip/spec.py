"""Finds a cell's pieces by name.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic mix.  Everything else is a file of its own,
found by name, so a later change adds files and edits none:

- ``configs/<config>.json``: the configuration's sizes, and ``family``,
  the name of its plain reference ``configs/<family>.py``;
- ``traffic/<traffic>.json``: the traffic mix's parameters; ``driver``
  names the module of this directory that runs it (``serving.py``,
  ``training.py``), so a new kind of traffic is a new file;
- ``workloads/<cell>.json``: what the cell fixes on top of the mix (rate
  and slots, or batch);
- ``metrics/<metric>.py``: the reader of one per-layer metric.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class SpecError(Exception):
    """A cell, configuration, traffic mix or metric that cannot be found."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    sizes: dict            # configs/<config>.json
    traffic: dict          # traffic/<traffic>.json
    params: dict           # workloads/<cell>.json
    end_to_end: tuple      # BENCHMARK.json entries this cell reports
    per_layer: tuple

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _read_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path.relative_to(ROOT)}") from e


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_bench() -> dict:
    return _read_json(ROOT / "BENCHMARK.json")


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_bench()
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"unknown workload {name!r} (known: {known})")
    row = rows[0]
    traffic = _read_json(HERE / "traffic" / f"{row['traffic']}.json")
    if not (HERE / f"{traffic['driver']}.py").is_file():
        raise SpecError(f"traffic {row['traffic']!r} names driver "
                        f"{traffic['driver']!r}, and there is no "
                        f"{traffic['driver']}.py")
    return Cell(
        name=name, config_name=row["config"], traffic_name=row["traffic"],
        chips=int(row["chips"]),
        sizes=_read_json(HERE / "configs" / f"{row['config']}.json"),
        traffic=traffic,
        params=_read_json(HERE / "workloads" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)),
    )


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots
    and hyphens, which ``import`` does not take)."""
    if not path.is_file():
        raise SpecError(f"missing file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    """The module that runs ``cell``'s traffic: ``<driver>.py`` beside
    this file, imported by its plain name so that it is the module the
    rest of the benchmark imports."""
    return importlib.import_module(cell.driver)


def reference(sizes: dict):
    """The plain reference module of a configuration's family."""
    return load_module(HERE / "configs" / f"{sizes['family']}.py")


def metric_reader(name: str):
    return load_module(HERE / "metrics" / f"{name}.py")
