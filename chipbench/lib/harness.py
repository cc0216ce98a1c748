"""Plumbing shared by every kind of cell: finding a cell's parts by
name, the device check, seeds, compile counting and the result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[2]          # the checkout
BENCH = ROOT / "chipbench"
OUT = ROOT / ".chipbench"                            # traces, scratch


def read_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "chipbench_" + "_".join(Path(path).relative_to(BENCH).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), conf["name"],
                read_json(ROOT / conf["file"]), w["traffic"],
                read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                e2e, per_layer)


def require_devices(chips: int):
    """JAX's devices, or exit non-zero without a result when it finds
    no accelerator or fewer chips than the cell needs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator found: {e}")
    if devices[0].platform in ("cpu",):
        raise SystemExit(f"no accelerator found: JAX sees "
                         f"{devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def seed_words(seed: int, *salt: int) -> np.ndarray:
    """Two uint32 words from any non-negative seed (and a salt), for
    ``jax.random.wrap_key_data``."""
    return np.random.SeedSequence([int(seed), *salt]).generate_state(
        2, np.uint32)


def jax_key(seed: int, *salt: int):
    import jax
    return jax.random.wrap_key_data(seed_words(seed, *salt))


class CompileCount:
    """Compilations and persistent-cache loads while installed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


class Phases:
    """Seconds of each phase of set-up, from the process's start ``t0``;
    ``total`` is the set-up time up to the last mark."""

    def __init__(self, t0: float):
        self.t0 = self.last = t0
        self.seconds: Dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = round(now - self.last, 3)
        self.last = now

    @property
    def total(self) -> float:
        return self.last - self.t0


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


@dataclasses.dataclass
class Check:
    """One number compared with its limit: passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a kind's ``run`` hands back to run.py."""
    end_to_end: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    run: Any = None                    # what the per-layer readers read
    busy_s: Optional[float] = None     # traced runs only
    window_s: Optional[float] = None
    breakdown: Optional[dict] = None
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)
