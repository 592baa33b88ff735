"""Device time per training step in the layer scan's own plumbing: the
slices of each layer's weights and the stacking of its residuals and
gradients, operations under the scope ``layers`` (``models/lm.py``
``forward_hidden``) and no sub-layer or GEMM scope
(``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "layer_scan")
