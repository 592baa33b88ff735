"""The system under test, built as a configuration file states it."""

from __future__ import annotations

import dataclasses
from typing import Dict

# configuration-file keys (the published config.json names) -> ModelConfig
_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "head_dim": "head_dim", "rms_norm_eps": "norm_eps",
         "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings"}


def model_config(conf: Dict):
    """The program's ModelConfig for ``conf['arch_id']`` with every size the
    file gives in place of the program's own."""
    from repro.configs import get_config

    base = get_config(conf["arch_id"])
    return dataclasses.replace(base, **{
        v: conf[k] for k, v in _KEYS.items() if k in conf})


def policy(spec: Dict):
    from repro.core.precision import get_policy

    extra = {k: v for k, v in spec.items() if k != "mode"}
    return get_policy(spec["mode"], **extra)


def model(conf: Dict, pol, mix: Dict):
    from repro.models import build_model
    from repro.models.lm import LMCallOptions

    opts = {k: mix[k] for k in ("q_chunk", "kv_chunk", "remat", "ce_chunk")
            if k in mix}
    return build_model(model_config(conf), pol, LMCallOptions(**opts))
