"""BENCHMARK.json and the files it names.

A cell of ``workloads`` names a configuration and a traffic mix; the harness
finds ``benchmark/configs/<config>.json``, ``benchmark/traffic/<traffic>.json``
and, for every per-layer metric, ``benchmark/layer_metrics/<metric>.py`` by
that name alone. The configuration names its family, found as
``benchmark/families/<family>.py``: the one place that knows an architecture
(the program's config and weights, the plain reference, the arithmetic). A
device trace is costed by every file of ``benchmark/kernels/``. Adding a cell,
a configuration, a family, a kernel, a mix or a per-layer metric is adding
files and entries; nothing here knows any of them.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
import tempfile
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = "benchmark"

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_DRIVERS = ("serve_open", "serve_closed", "train")


def configure_environment(root: str = ROOT) -> None:
    """What every process that starts the runtime for a cell sets first
    (workers inherit it). One compile cache at a fixed place inside the
    checkout (its path is part of its key) unless the machine names one,
    every program in it however quick its compile; the runtime's session
    files under this run's own temporary directory; and a node that is
    declared dead only after a minute without a heartbeat: a host that stalls
    for ten seconds shows as lateness and does not lose the run (seen once
    on the chip, PR 22)."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    os.environ.setdefault("RT_NODE_DEATH_TIMEOUT_S", "60")
    os.environ.setdefault("RT_SESSION_DIR_ROOT",
                          os.path.join(tempfile.gettempdir(), "ray_tpu"))


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, is not what the harness can run."""


def _load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{path}: no such file") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: not JSON ({e})") from None


def _check_name(what: str, name: Any) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"{what} {name!r}: a name is 1-64 of a-z A-Z 0-9 _ . - "
                        f"and does not start with . or -")
    return name


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    """BENCHMARK.json, checked as far as the harness depends on it."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    configs = {_check_name("config", c.get("name")): c
               for c in bench.get("configs", [])}
    if len(configs) != len(bench.get("configs", [])):
        raise SpecError("two configurations share a name")
    cells: Dict[str, Dict[str, Any]] = {}
    pairs = set()
    for w in bench.get("workloads", []):
        name = _check_name("workload", w.get("name"))
        _check_name("traffic", w.get("traffic"))
        if name in cells:
            raise SpecError(f"workload {name!r} appears twice")
        if w.get("config") not in configs:
            raise SpecError(f"workload {name!r}: no configuration "
                            f"{w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            raise SpecError(f"workload {name!r}: chips is 1 or 4")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            raise SpecError(f"{pair} appears in two workloads")
        pairs.add(pair)
        cells[name] = w
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        raise SpecError(f"{four} of {len(cells)} workloads ask for 4 chips")
    seen = set()
    e2e = {m.get("name") for m in bench.get("end_to_end", [])}
    for kind in ("end_to_end", "per_layer"):
        for m in bench.get(kind, []):
            name = _check_name(f"{kind} metric", m.get("name"))
            if name in seen:
                raise SpecError(f"metric {name!r} appears twice")
            seen.add(name)
            if not isinstance(m.get("unit"), str) or not _UNIT.match(m["unit"]):
                raise SpecError(f"metric {name!r}: unit {m.get('unit')!r} is "
                                f"not 1-16 of a-z A-Z 0-9 _ / % . -")
            if m.get("better") not in ("lower", "higher"):
                raise SpecError(f"metric {name!r}: better is lower or higher")
            if m.get("source") not in _SOURCES:
                raise SpecError(f"metric {name!r}: source {m.get('source')!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    raise SpecError(f"metric {name!r} lists no such "
                                    f"workload {w!r}")
            if kind == "per_layer" and m.get("moves") not in e2e:
                raise SpecError(f"metric {name!r} moves {m.get('moves')!r}, "
                                f"which is no end-to-end metric")
    if "setup_s" not in e2e:
        raise SpecError("end_to_end has no setup_s")
    return bench


def metrics_of(bench: Dict[str, Any], kind: str, cell: str
               ) -> List[Dict[str, Any]]:
    """The ``kind`` metrics that cell reports: those that list it, or list
    nothing. A per-layer metric is reported only where what it moves is."""
    mine = [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
    if kind == "per_layer":
        e2e = {m["name"] for m in metrics_of(bench, "end_to_end", cell)}
        mine = [m for m in mine if m["moves"] in e2e]
    return mine


def _load_module(kind: str, name: str, root: str, needs: List[str]) -> ModuleType:
    """``benchmark/<kind>/<name>.py``, loaded under a name of its own, with
    every callable of ``needs``."""
    _check_name(kind, name)
    path = os.path.join(root, BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"{kind} {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for attr in needs:
        if not callable(getattr(module, attr, None)):
            raise SpecError(f"{path} defines no {attr}()")
    return module


def load_reader(name: str, root: str = ROOT) -> Callable[[Dict[str, Any]], Any]:
    """``benchmark/layer_metrics/<name>.py``'s ``read(run)``."""
    return _load_module("layer_metrics", name, root, ["read"]).read


FAMILY_API = ["program_config", "init_params", "logits", "token_margins", "loss",
              "matmul_params", "attention_flops_per_token",
              "cache_bytes_per_position"]


@functools.lru_cache(maxsize=None)
def load_family(name: str, root: str = ROOT) -> ModuleType:
    """``benchmark/families/<name>.py``: what a configuration file's
    ``family`` names. Importing one imports neither JAX nor the program
    (the parent reads its arithmetic); its functions do. One module object
    per file, because its reference block keys a compiled program. A family
    that builds on another loads it from ``root_of(__file__)``."""
    return _load_module("families", name, root, FAMILY_API)


def root_of(path: str) -> str:
    """The checkout that a file of ``benchmark/<kind>/`` lies in."""
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(path))))


def load_kernels(root: str = ROOT) -> Dict[str, ModuleType]:
    """Every ``benchmark/kernels/<kernel>.py`` by the file's name, each with
    a ``match(event_name)``: the directory is the list."""
    folder = os.path.join(root, BENCH_DIR, "kernels")
    names = sorted(f[:-3] for f in os.listdir(folder)
                   if f.endswith(".py") and not f.startswith("_"))
    return {n: _load_module("kernels", n, root, ["match"]) for n in names}


class Cell:
    """One workload with everything it names, loaded."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.bench = load_benchmark(root)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                            f"(has: {', '.join(sorted(cells))})")
        self.workload = cells[workload]
        self.name: str = workload
        self.chips: int = self.workload["chips"]
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == self.workload["config"])
        self.config: Dict[str, Any] = _load_json(os.path.join(root, entry["file"]))
        for key in ("source", "config", "reduced", "assumed", "family"):
            if key not in self.config:
                raise SpecError(f"{entry['file']}: no {key!r}")
        if self.config["source"] != entry["source"]:
            raise SpecError(f"{entry['file']}: source differs from "
                            f"BENCHMARK.json's")
        if sorted(self.config["reduced"]) != sorted(entry["reduced"]):
            raise SpecError(f"{entry['file']}: reduced differs from "
                            f"BENCHMARK.json's")
        self.traffic: Dict[str, Any] = _load_json(os.path.join(
            root, BENCH_DIR, "traffic", self.workload["traffic"] + ".json"))
        if self.traffic.get("driver") not in _DRIVERS:
            raise SpecError(f"traffic {self.workload['traffic']!r}: driver "
                            f"is one of {_DRIVERS}")
        self.end_to_end = metrics_of(self.bench, "end_to_end", workload)
        self.per_layer = metrics_of(self.bench, "per_layer", workload)
        self.readers = {m["name"]: load_reader(m["name"], root)
                        for m in self.per_layer}

    @property
    def family(self) -> ModuleType:
        return load_family(self.config["family"], self.root)

    @property
    def phase(self) -> str:
        """Which of a configuration's depths the mix's driver takes."""
        return "train" if self.traffic["driver"] == "train" else "serve"

    def n_layers(self) -> int:
        depth = self.config["reduced"].get("num_hidden_layers")
        if depth is None:
            return int(self.config["config"]["num_hidden_layers"])
        if self.phase not in depth:
            raise SpecError(f"configuration {self.config['name']!r} gives no "
                            f"{self.phase} depth")
        return int(depth[self.phase])


def layer_values(cell: Cell, run: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell whose reader found something."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in cell.per_layer:
        value: Optional[float] = cell.readers[m["name"]](run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
