"""Device time per training step in the optimizer: the global-norm clip and
AdamW over the float32 master weights, the scopes ``clip`` and
``optimizer`` that ``runtime/trainer.make_train_step`` opens. Self time of
the trace's operations by named scope (``chipbench/scopes.py``), per
step."""

from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "optimizer")
