"""Device time per training step in attention's core, forward, remat and
backward: the chunked online-softmax attention with its score and value
matmuls, under the scope ``attn_core`` that ``models/attention.attn_apply``
opens (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run):
    return scopes.ms_per_step(run, "attention_core")
