"""What every cell shares: finding a cell's files by name, the device
check, the compile clock, host spans, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration file names the system module (``bench/systems/<name>.py``)
that runs it; its traffic file is read by :mod:`bench.traffic`; each of its
per-layer metrics is a reader ``bench/metrics/<metric name>.py`` with a
``read(run) -> float | None``.  Adding a cell, a mix or a metric adds
files and entries and edits none.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# A fixed path inside the checkout: the path is part of the compile
# cache's key, so a directory that moved would never hit.
CACHE_DIR = os.path.join(ROOT, ".bench_cache")


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, name: str, bench: Optional[Dict] = None):
        self.bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; have "
                             f"{sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        cfg_entry = {c["name"]: c for c in self.bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", self.workload["traffic"] + ".json"))
        self.chips = int(self.workload["chips"])

    def system(self):
        name = self.config["system"]
        return _load_module(os.path.join(BENCH, "systems", name + ".py"),
                            f"bench_system_{name}")

    def _mine(self, metric: Dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    def per_layer(self) -> List[Dict]:
        return [m for m in self.bench["per_layer"] if self._mine(m)]

    @staticmethod
    def reader(metric: str):
        return _load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                            "bench_metric_" + metric.replace(".", "_"))


def prepare_env() -> None:
    """Before jax is imported: the program on the path, the compile cache
    in the checkout, every compiled program kept."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int, platform: str = "tpu") -> Dict:
    """The device record, or :class:`NoDevice` when JAX sees no TPU or
    fewer than ``n`` chips."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"no accelerator: {e}")
    if devs[0].platform != platform:
        raise NoDevice(f"no {platform.upper()}: jax runs on "
                       f"{devs[0].platform}")
    if len(devs) < n:
        raise NoDevice(f"the cell needs {n} chips; jax sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def peak_bytes(devices) -> Optional[int]:
    """Peak allocator bytes on the fullest of ``devices``."""
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


class CompileClock:
    """Counts and times the backend compiles jax reports."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


class Spans:
    """Host spans the benchmark records around its calls into the
    program, as profiler annotations: a trace shows them beside the
    device's ops, and idle gaps are labelled with them."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


def check(value: float, limit: float) -> Dict:
    return {"value": value, "limit": limit}


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict, device: Dict, checks: Dict,
                breakdown: Optional[Dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks      # last: each number compared, beside its limit
    return json.dumps(out)
