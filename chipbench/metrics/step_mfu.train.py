"""Model FLOP utilisation of the training step: the forward and backward
flops each trained token needs (``counts.train_flops_per_token``, no
recomputation) times the tokens per second of the traced window, over the
cell's compute peak (``peaks.json`` rule)."""

from chipbench import counts


def read(run):
    if "tokens_per_s" not in run.layer:
        return None
    peak = counts.compute_peak(run.device["kind"], run.cell.config["policy"])
    return 100.0 * run.layer["flops_per_token"] * run.layer["tokens_per_s"] \
        / (peak * run.cell.chips)
