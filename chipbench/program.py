"""The system under test, built as a configuration file states it.

The program's ``repro.configs.from_published(conf)``, where it has one,
builds the ``ModelConfig`` from the file's published ``config.json`` keys
and its ``arch_id``; until then :data:`_KEYS` stands in for it. Either
way the driver holds the model's parameter tree to the one the cell's
reference reads from the same file, so a size the program leaves out
stops the run.
"""

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
    from repro import configs

    reader = getattr(configs, "from_published", None)
    if reader is not None:
        return reader(conf)
    base = configs.get_config(conf["arch_id"])
    return dataclasses.replace(base, **{
        v: conf[k] for k, v in _KEYS.items() if k in conf})


def policy(spec: Dict):
    from repro.core.precision import get_policy

    extra = {k: v for k, v in spec.items() if k != "mode"}
    return get_policy(spec["mode"], **extra)


def model(conf: Dict, pol, mix: Dict):
    """The model with every traffic key that names an ``LMCallOptions``
    field passed through."""
    from repro.models import build_model
    from repro.models.lm import LMCallOptions

    opts = {f.name: mix[f.name] for f in dataclasses.fields(LMCallOptions)
            if f.name in mix}
    return build_model(model_config(conf), pol, LMCallOptions(**opts))
