"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files live at ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json`` (a mix may name another as its ``base``
and change some of its keys), and each per-layer metric's reader at
``bench/metrics/<metric>.py``. Adding a cell, configuration, mix or metric
adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib

# code (metric readers, architecture modules) lives beside this file; the
# data (BENCHMARK.json, configuration, traffic and peak files) under ROOT
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    return json.loads(path.read_text())


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config_entry(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config named {name!r} in BENCHMARK.json")


def load_config(bm: dict, name: str) -> dict:
    entry = config_entry(bm, name)
    return json.loads((ROOT / entry["file"]).read_text())


def load_traffic(name: str) -> dict:
    """A mix's parameters. A mix that names ``base`` is that mix with the
    keys it gives put over it: ``chat.failstop`` is ``chat`` with a fault
    injected, and cannot drift from it."""
    mix = json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                     .read_text())
    if "base" in mix:
        mix = {**load_traffic(mix.pop("base")), **mix}
    return mix


def load_peaks() -> dict:
    return json.loads((ROOT / "bench" / "peaks.json").read_text())


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric and model file names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(arch: str):
    return importlib.import_module(f"bench.models.{arch}")


def metric_reader(name: str):
    mod = load_module(BENCH / "metrics" / f"{name}.py",
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read


def metrics_for(bm: dict, cell_name: str, kind: str) -> list:
    """The metric entries a cell reports: ``kind`` is ``end_to_end`` or
    ``per_layer``. A metric without ``workloads`` holds for every cell."""
    return [m for m in bm[kind]
            if "workloads" not in m or cell_name in m["workloads"]]
