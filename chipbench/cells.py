"""Find a cell's parts by name: `BENCHMARK.json` names the cell, its
configuration file, its traffic mix (`chipbench/traffic/<mix>.json`),
its end-to-end metrics and its per-layer metrics, whose readers are
`chipbench/metrics/<metric>.py`. Adding a cell, a configuration, a mix
or a metric adds files and entries; nothing here names one."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path


class CellError(ValueError):
    """The benchmark names something that is not there."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json
    per_layer: list
    root: Path

    def reader(self, metric: str):
        """The `read(ctx)` function of a per-layer metric."""
        return load_reader(self.root, metric)


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"missing file {path}") from None


def load_benchmark(root: Path) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def _applies(metric: dict, cell: str, reported=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def resolve(root: Path, bench: dict, name: str) -> Cell:
    root = Path(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; "
                        f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"{name}: no configuration {w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "chipbench" / "traffic"
                        / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    for m in per_layer:
        _reader_path(root, m["name"])
    return Cell(name, int(w["chips"]), w["config"], w["traffic"], config,
                traffic, e2e, per_layer, root)


def _reader_path(root: Path, metric: str) -> Path:
    path = Path(root) / "chipbench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise CellError(f"no reader {path} for metric {metric!r}")
    return path


def load_reader(root: Path, metric: str):
    path = _reader_path(root, metric)
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
