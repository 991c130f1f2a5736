"""Finds a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; the files follow from those names.

``bench/configs/<config>.json``   sizes, departures, engine settings, and
                                  under ``"arch"`` the architecture's name
``bench/arch/<arch>.py``          what knows the architecture's shape: the
                                  program's config, the reference's layers,
                                  the K/V element count, the work counts
                                  and the kernel table (``bench/arch/dense.py``
                                  lists the names a module gives)
``bench/traffic/<mix>.json``      the mix's parameters
``bench/cells/<cell>.json``       optional: the cell's own numbers (an
                                  arrival rate, the limits of ``correct``)
                                  laid over the mix's
``bench/metrics/<metric>.py``     one reader per per-layer metric

Adding a configuration, an architecture, a mix, a cell or a metric is
adding files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import importlib.util
import json
import os
import re
import sys
import types
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    arch: types.ModuleType          # bench/arch/<config["arch"]>.py
    mix: dict                       # the mix's parameters, cell's laid over
    checks: dict                    # limits of ``correct``
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> Cell:
    bench_dir = os.path.join(root, "bench")
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    module = arch(config, bench_dir, cfg_entry["file"])
    mix = _load_json(os.path.join(bench_dir, "traffic",
                                  w["traffic"] + ".json"))
    cell_path = os.path.join(bench_dir, "cells", workload + ".json")
    own = _load_json(cell_path) if os.path.exists(cell_path) else {}
    checks = own.pop("checks", {})
    mix = {**mix, **own}

    def applies(m: dict) -> bool:
        return workload in m.get("workloads", [workload])

    return Cell(name=workload, chips=int(w["chips"]), config=config,
                arch=module, mix=mix, checks=checks,
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)],
                bench_dir=bench_dir)


@functools.lru_cache(maxsize=None)
def _module(path: str, prefix: str) -> types.ModuleType:
    """The module in the file ``path``, loaded once, under a name of its
    own (a dataclass looks its module up in ``sys.modules``)."""
    stem = re.sub(r"[^A-Za-z0-9_]", "_",
                  os.path.splitext(os.path.basename(path))[0])
    name = f"{prefix}{stem}_{hashlib.sha1(path.encode()).hexdigest()[:12]}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH
                  ) -> Callable[[dict], Optional[float]]:
    """``read(record)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    return _module(path, "bench_metric_").read


def arch(config: dict, bench_dir: str = BENCH,
         where: str = "the configuration") -> types.ModuleType:
    """The architecture module ``bench/arch/<arch>.py`` that the
    configuration's ``"arch"`` key names (``where``: the configuration's
    file, for the error)."""
    name = config.get("arch")
    d = os.path.join(bench_dir, "arch")
    path = os.path.join(d, f"{name}.py")
    if not isinstance(name, str) or not re.fullmatch(
            r"[A-Za-z0-9_][A-Za-z0-9_-]*", name) or not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(d) if f.endswith(".py")) \
            if os.path.isdir(d) else []
        raise KeyError(f"{where} names architecture {name!r} under \"arch\", "
                       f"and bench/arch/ holds no such module (have {have})")
    return _module(path, "bench_arch_")


def read_metrics(cell: Cell, record: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"], cell.bench_dir)(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peaks(device_kind: str, bench_dir: str = BENCH) -> dict:
    table = _load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
