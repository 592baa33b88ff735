#!/usr/bin/env python3
"""Readings that set a training cell's limits.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 [--fault half_batch] [--out readings.jsonl]
    python3 chipbench/calibrate.py --workload <cell> --reorder-seeds 7,8
    python3 chipbench/calibrate.py --workload <cell> --keep-trace DIR \
        --seconds 4

In one process on the chip, it runs the cell's set-up and correctness
comparison once per seed: the program as the configuration states it
(``--seeds``, with ``--fault`` a fault of ``chipbench/faults.py`` planted
in it) and the program with the configuration's ``control_policy``, one
precision below (``--control-seeds``). No window is measured. One JSON
line per reading goes to standard output and to ``--out``, with the
compiled step's memory and the set-up's phases.

``--reorder-seeds`` compares the reference with itself: the same three
steps on the same batches with the batch's sequences in reverse order.
That changes only the order of the float32 sums over the batch (the
rounding groups of every operand stay as they are), so it reads how far
two sound computations of the configuration drift apart by summation
order alone. ``--attention-seeds`` compares the reference with itself
with attention's products left unrounded: how far a difference of
bfloat16 size in attention alone moves the readings.

``--keep-trace DIR`` makes one traced run with a window of ``--seconds``
and keeps its ``.xplane.pb`` in DIR (``tests/data`` holds one, compressed).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from chipbench import faults, harness  # noqa: E402


def _emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def readings(cell, seeds, control_seeds, out, fault=None):
    control = cell.config["control_policy"]
    plant = faults.TRAIN[fault] if fault else contextlib.nullcontext
    for kind, seed_list in (("program", seeds), ("control", control_seeds)):
        for seed in seed_list:
            t = time.perf_counter()
            extra = {"program_policy": control} if kind == "control" else {}
            with plant():
                run = harness.execute(cell, seed, 0.0, False,
                                      time.perf_counter(), **extra)
            rec = {"cell": cell.name, "kind": kind, "seed": seed,
                   "fault": fault,
                   "checks": {c["name"]: c["value"] for c in run.checks},
                   "memory_peak_bytes": run.memory_peak_bytes,
                   "step_memory": run.layer.get("step_memory"),
                   "setup_phases": run.layer.get("setup_phases"),
                   "seconds": time.perf_counter() - t}
            rec.update(run.layer.get("calibration", {}))
            _emit(out, rec)


def self_compare(cell, seeds, out, kind):
    """The reference against itself: with each batch's rows reversed
    (``reorder``) or with attention's products unrounded (``attention``)."""
    import jax.numpy as jnp

    from chipbench import gen
    from chipbench.reference import qwen2 as reference

    conf, mix = cell.config, cell.traffic
    other = dict(conf, attention_operands="fp32") if kind == "attention" \
        else conf
    compare = harness.load_module(cell.driver_path).compare
    kw = dict(lr=mix["lr"], clip=mix["grad_clip"], ce_chunk=mix["ce_chunk"])
    for seed in seeds:
        t = time.perf_counter()
        key = jnp.asarray(gen.seed_words(seed))
        data = gen.SyntheticLM(conf["vocab_size"], mix["seq"], mix["batch"],
                               seed)
        batches = [data.batch_at(i) for i in range(mix["check_steps"])]
        if kind == "reorder":
            first = [{k: v[::-1].copy() for k, v in b.items()}
                     for b in batches]
        else:
            first = batches
        a = reference.train_readings(other, conf["policy"], key, first,
                                     keep_grad=True, **kw)
        b = reference.train_readings(conf, conf["policy"], key, batches,
                                     grad=a.pop("grad_arrays"), **kw)
        _emit(out, {"cell": cell.name, "kind": kind, "seed": seed,
                    "readings": compare(a["losses"], a["grad"], a["change"],
                                        b),
                    "grad_diff": b["grad_diff"], "grad": b["grad"],
                    "seconds": time.perf_counter() - t})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--reorder-seeds", default="")
    ap.add_argument("--attention-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--fault", default=None, choices=sorted(faults.TRAIN),
                    help="plant a fault of chipbench/faults.py in the program")
    ap.add_argument("--keep-trace", default=None, metavar="DIR")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.Cell(bench, args.workload)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    if args.keep_trace:
        cell.traffic = dict(cell.traffic, trace_seconds=args.seconds)
        run = harness.execute(cell, 777, args.seconds, True,
                              time.perf_counter(),
                              keep_trace=Path(args.keep_trace))
        _emit(args.out, harness.result_line(run))
        return 0
    if args.seeds or args.control_seeds:
        readings(cell, ints(args.seeds), ints(args.control_seeds), args.out,
                 args.fault)
    if args.reorder_seeds:
        self_compare(cell, ints(args.reorder_seeds), args.out, "reorder")
    if args.attention_seeds:
        self_compare(cell, ints(args.attention_seeds), args.out, "attention")
    return 0


if __name__ == "__main__":
    sys.exit(main())
