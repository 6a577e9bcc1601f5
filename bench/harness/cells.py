"""Find a cell's pieces by name: each is a file of its own.

A benchmark root is a directory laid out like ``bench/``:

    configs/<config>.json     a model configuration as it is run
    traffic/<traffic>.json    a traffic mix (parameters of the generator)
    metrics/<metric>.py       one metric reader: ``read(record) -> float | None``

``BENCHMARK.json`` (at the checkout root, or in an extra root) maps a cell
name to its configuration, traffic and chips, and lists the metrics.  Extra
roots are searched before the checkout's own, so a configuration, a mix or
a reader is added by adding a file, never by editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _benchmark_json(roots: list[Path], workload: str) -> dict:
    """The first BENCHMARK.json (extra roots, then the checkout) naming
    ``workload``."""
    for root in [*roots, CHECKOUT]:
        path = root / "BENCHMARK.json"
        if not path.is_file():
            continue
        spec = json.loads(path.read_text())
        if any(w["name"] == workload for w in spec.get("workloads", [])):
            return spec
    raise SystemExit(f"no BENCHMARK.json names the workload {workload!r}")


def find_file(roots: list[Path], kind: str, name: str, suffix: str) -> Path:
    for root in [*roots, BENCH]:
        path = root / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise SystemExit(f"no {kind}/{name}{suffix} in {[str(r) for r in [*roots, BENCH]]}")


def _metrics(spec: dict, kind: str, cell: str) -> tuple[Metric, ...]:
    """The metrics of ``kind`` the cell reports: those with no ``workloads``
    list, and those whose list names the cell."""
    return tuple(Metric(m["name"], m["unit"]) for m in spec.get(kind, [])
                 if cell in m.get("workloads", [cell]))


def load_cell(workload: str, roots: list[Path]) -> Cell:
    spec = _benchmark_json(roots, workload)
    entry = next(w for w in spec["workloads"] if w["name"] == workload)
    config = json.loads(find_file(roots, "configs", entry["config"], ".json").read_text())
    traffic = json.loads(find_file(roots, "traffic", entry["traffic"], ".json").read_text())
    return Cell(
        name=workload,
        traffic_name=entry["traffic"],
        chips=int(entry["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=_metrics(spec, "end_to_end", workload),
        per_layer=_metrics(spec, "per_layer", workload),
    )


def load_reader(roots: list[Path], name: str):
    """Import ``metrics/<name>.py`` → its ``read`` function."""
    path = find_file(roots, "metrics", name, ".py")
    module_name = "bench_metric_" + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
