"""Roofline share of the training step's matmuls: the least time the chip
needs for the matmul flops the step's mathematics asks for (weight GEMMs
and attention products, forward and backward, no recomputation) at the
cell's compute peak, over the device time of the trace's matmul
operations per step."""

from chipbench import counts, reduce


def read(run):
    t = run.layer.get("trace")
    if not t or not run.layer.get("steps"):
        return None
    mm, _ = reduce.matmul_seconds(t)
    if mm <= 0:
        return None
    mix = run.cell.traffic
    peak = counts.compute_peak(run.device["kind"], run.cell.config["policy"])
    need = run.layer["flops_per_token"] * mix["batch"] * mix["seq"] / peak
    return 100.0 * need / (mm / run.layer["steps"])
