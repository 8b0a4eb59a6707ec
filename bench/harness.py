"""What every cell shares: finding its files by name, the chip check,
the compile cache, compile counting, peaks, and the result line."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

# the labels the harness wraps around each call into a layer; the trace
# reduction splits device idle time over them
LABELS = ("generate", "wait_arrival", "serve_call", "map_round",
          "host_to_device", "device_to_host")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict

    def end_to_end(self) -> List[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those with no list whose end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def find_cell(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config, traffic, spec)


def driver_module(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``; its ``read(run)`` returns a number or
    None where it finds nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(chips: int):
    """The first ``chips`` TPU devices; raises NoChip otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {devices[0].platform!r}; "
                     "the benchmark runs only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips and JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache, at ``JAX_COMPILATION_CACHE_DIR``
    where that is set and at the fixed ``<checkout>/.jax_cache``
    otherwise; every program is kept, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads while active."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.active = False
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if not self.active:
            return
        if name == self.COMPILE:
            self.compiles += 1
        elif name == self.CACHE_LOAD:
            self.cache_loads += 1

    def __enter__(self):
        self.active = True
        return self

    def __exit__(self, *exc):
        self.active = False
        return False


@dataclass
class Check:
    """One number compared with its limit; ``ok`` iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    attempted: int
    failed: int
    checks: List[Check]
    end_to_end: Dict[str, float] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(c.ok for c in self.checks)


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def result_line(outcome: Outcome, metrics: dict, device: dict,
                breakdown: Optional[dict] = None) -> str:
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return json.dumps(line)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
