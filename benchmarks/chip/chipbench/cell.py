"""A cell by name: its entry in ``BENCHMARK.json``, configuration, model
kind, traffic, per-layer metric readers, and the device it may run on."""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip


class RefusedError(Exception):
    """The run cannot stand for the cell: no result may be printed."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    kind: ModuleType                 # kinds/<config's kind>.py
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def load(name: str, bench_file: Path, harness_dir: Path = HERE) -> Cell:
    """The cell ``name`` of ``bench_file``; its configuration file lies
    where the entry says, relative to the file's directory, its model kind
    under ``harness_dir/kinds`` and its traffic mix under
    ``harness_dir/traffic``."""
    bench = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RefusedError(f"no workload {name!r} in {bench_file}; "
                           f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    root = Path(bench_file).resolve().parent
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((Path(harness_dir) / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                kind=load_kind(config.get("kind"), harness_dir), mix=mix,
                end_to_end=[], per_layer=[])
    cell.end_to_end = [m for m in bench["end_to_end"] if cell.reports(m)]
    cell.per_layer = [m for m in bench["per_layer"] if cell.reports(m)]
    return cell


def load_kind(kind: Optional[str], harness_dir: Path = HERE) -> ModuleType:
    """``kinds/<kind>.py``, loaded by path: everything the harness knows of
    a model kind.  A configuration without a kind, or with one that has no
    module, is refused; a kind is never assumed."""
    if not isinstance(kind, str) or not kind:
        raise RefusedError("the configuration names no model \"kind\"")
    path = Path(harness_dir) / "kinds" / f"{kind}.py"
    if Path(kind).name != kind or not path.is_file():
        raise RefusedError(f"unknown model kind {kind!r}: no {path}")
    return _load_module("chipbench_kind_" + kind.replace(".", "_"), path)


def engine_device(config: dict, chips: int) -> str:
    """The engine's device profile: the configuration's on one chip, and
    the program's ``mesh:<profile>:<n>`` over it on ``n`` chips."""
    return config["device"] if chips == 1 else (
        f"mesh:{config['device']}:{chips}")


def _load_module(name: str, path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_peaks(path: Optional[Path] = None) -> Dict[str, dict]:
    data = json.loads(Path(path or HERE / "peaks.json").read_text())
    return {k: v for k, v in data.items() if isinstance(v, dict)}


def check_devices(devices, chips: int, peaks: Dict[str, dict]) -> dict:
    """The accelerator the run stands on, or RefusedError: no TPU, fewer
    chips than the cell asks for, or a device kind with no published
    peaks."""
    if not devices:
        raise RefusedError("JAX found no devices")
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        raise RefusedError(f"no accelerator: JAX runs on {platform!r}")
    if len(devices) < chips:
        raise RefusedError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devices)}")
    if kind not in peaks:
        raise RefusedError(f"device kind {kind!r} is not in peaks.json "
                           f"({sorted(peaks)})")
    return {"platform": platform, "kind": kind, "count": len(devices)}


def metric_reader(name: str, harness_dir: Path = HERE) -> Callable:
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = Path(harness_dir) / "metrics" / f"{name}.py"
    return _load_module("chipbench_metric_" + name.replace(".", "_"),
                        path).read
