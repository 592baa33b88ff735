"""A copy of the benchmark's cells at a size a CPU test can hold.

Every cell of BENCHMARK.json is rebuilt in a temporary directory with the
same traffic, policies and limits, at the configuration's published widths
but 2 layers and a 4,096-token vocabulary. The CPU runs a float32 dot in
full float32, not as the TPU's one bfloat16 pass, so the copy states its
attention products as float32. The harness runs it as it would on the
chip, minus the look for a chip.
"""

from __future__ import annotations

import copy
import json
from pathlib import Path

from chipbench import harness

TINY = {"num_hidden_layers": 2, "vocab_size": 4096,
        "attention_operands": "fp32"}
TRAIN = {"trace_seconds": 1}


def bench_copy(tmp: Path, limits=None) -> dict:
    """Write the tiny copy under ``tmp``; returns its BENCHMARK dict."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for sub in ("configs", "traffic", "limits"):
        (tmp / sub).mkdir(parents=True, exist_ok=True)
    for c in bench["configs"]:
        conf = json.loads((harness.ROOT / c["file"]).read_text())
        conf.update(TINY)
        c["file"] = f"configs/{c['name']}.json"
        (tmp / c["file"]).write_text(json.dumps(conf))
    for w in bench["workloads"]:
        mix = json.loads((harness.HERE / "traffic" /
                          f"{w['traffic']}.json").read_text())
        mix.update(TRAIN)
        (tmp / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(mix))
        name = f"{w['config']}.{w['traffic']}.json"
        lim = json.loads((harness.HERE / "limits" / name).read_text())
        lim = copy.deepcopy(limits.get(name, lim) if limits else lim)
        (tmp / "limits" / name).write_text(json.dumps(lim))
    return bench


def no_chip(chips: int) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": "TPU v5 lite",
            "count": len(jax.devices())}


def run_cell(tmp: Path, bench: dict, cell: str, seed: int = 7,
             seconds: float = 1.0, trace: bool = False, **program):
    """Run ``cell`` of the tiny copy; ``program`` sets Run attributes
    (such as ``program_policy``) before the driver starts."""
    c = harness.Cell(bench, cell, root=tmp, base=tmp)
    return harness.execute(c, seed, seconds, trace, 0.0,
                           device_check=no_chip, **program)
