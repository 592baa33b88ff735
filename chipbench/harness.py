"""The harness: resolve a cell by name, run its driver, print the result.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: the model's published sizes, its numerics
  ``policy`` and the ``control_policy`` one precision below;
* ``traffic/<mix>.json``: the mix's parameters; its ``kind`` names the
  driver ``drivers/<kind>.py`` that runs such a mix;
* ``limits/<config>.<mix>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from;
* ``metrics/<metric>.py``: the reader of one per-layer metric, ``read(run)
  -> float | None``.

The result line has the keys ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared, with its limit.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
COMPILE_CACHE = ROOT / ".jax_cache"
WORK_DIR = ROOT / ".chipbench_work"


class BenchError(RuntimeError):
    """The cell cannot run here; the run exits non-zero with no result."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> Dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


class Cell:
    """A workload of BENCHMARK.json with its files resolved by name."""

    def __init__(self, bench: Dict, name: str, root: Path = ROOT,
                 base: Path = HERE):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        self.bench = bench
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _read_json(root / self.config_entry["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = _read_json(base / "traffic" / f"{self.traffic_name}.json")
        self.driver_path = HERE / "drivers" / f"{self.traffic['kind']}.py"
        if not self.driver_path.is_file():
            raise BenchError(f"no driver for traffic kind "
                             f"{self.traffic['kind']!r}")
        self.limits = _read_json(
            base / "limits" / f"{self.entry['config']}.{self.traffic_name}.json")
        self.chips = int(self.entry["chips"])

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


class Run:
    """What a driver is given, and what it fills in."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.e2e: Dict[str, float] = {}
        self.layer: Dict[str, Any] = {}
        self.checks: List[Dict[str, Any]] = []
        self.attempted = 0
        self.failed = 0
        # the program's numerics where they differ from the configuration's
        # (the precision control of a calibration), else None
        self.program_policy: Optional[Dict] = None
        # calibration only: a directory to keep the trace in
        self.keep_trace: Optional[Path] = None
        self.memory_peak_bytes = 0
        self.device: Dict[str, Any] = {}
        self.breakdown: Optional[Dict] = None
        self._compiles = 0
        self._counting = False

    # -- compilations inside the measured window -----------------------
    def count_compiles(self, on: bool) -> int:
        self._counting = on
        return self._compiles

    def _on_event(self, event: str, *args, **kwargs) -> None:
        if self._counting and event.endswith("jaxpr_to_mlir_module_duration"):
            self._compiles += 1

    # -- correctness ----------------------------------------------------
    def check(self, name: str, value: float) -> None:
        """Compare a number with its limit from the cell's limits file."""
        limit = float(self.cell.limits[name]["limit"])
        self.checks.append({"name": name, "value": float(value),
                            "limit": limit})

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks)

    def read_memory_peak(self) -> None:
        import jax

        self.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()[:self.cell.chips])


def require_chips(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it; refuses anything but enough TPUs."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {d.platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def read_layers(run: Run) -> Dict[str, Dict]:
    out = {}
    for m in run.cell.per_layer():
        path = HERE / "metrics" / f"{m['name']}.py"
        value = load_module(path).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, device_check=require_chips, **program) -> Run:
    """Run the cell's driver; returns the filled-in :class:`Run`.
    ``program`` sets attributes of the run before the driver starts."""
    import jax

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no program under {src}: run from a checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    run = Run(cell, seed, seconds, trace, t_start)
    for k, v in program.items():
        setattr(run, k, v)
    jax.monitoring.register_event_duration_secs_listener(run._on_event)
    run.device = device_check(cell.chips)
    enable_compile_cache()
    WORK_DIR.mkdir(exist_ok=True)
    load_module(cell.driver_path).run(run)
    gc.collect()
    return run


def result_line(run: Run) -> Dict[str, Any]:
    if run.trace:
        metrics = read_layers(run)
    else:
        metrics = {}
        for m in run.cell.end_to_end():
            if m["name"] not in run.e2e:
                raise BenchError(f"the driver measured no {m['name']}")
            metrics[m["name"]] = {"value": float(run.e2e[m["name"]]),
                                  "unit": m["unit"]}
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    if run.trace:
        device["busy_s"] = run.layer["trace"]["busy_s"]
        device["window_s"] = run.layer["trace"]["window_s"]
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace and run.breakdown:
        out["breakdown"] = run.breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in run.checks}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = _read_json(ROOT / "BENCHMARK.json")
        cell = Cell(bench, args.workload)
        run = execute(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
        line = result_line(run)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for c in run.checks:
        print(f"check {c['name']} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
