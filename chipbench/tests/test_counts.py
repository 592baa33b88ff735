"""Counts of work and the table of peaks."""

import json

import jax
import numpy as np
import pytest

from chipbench import counts, harness, program

CONFIGS = sorted((harness.HERE / "configs").glob("*.json"))


def _conf(name):
    return json.loads((harness.HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_gemm_params_match_the_models_matmul_weights(path):
    conf = json.loads(path.read_text())
    pol = program.policy(conf["policy"])
    model = program.model(conf, pol, {})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    n = 0
    for p, x in flat:
        leaf = str(getattr(p[-1], "key", p[-1]))
        if leaf in ("w", "emb"):          # matmul weights; emb is the head
            n += int(np.prod(x.shape))
    assert counts.gemm_params(conf) == n


def test_qwen2_0_5b_gemm_params_by_hand():
    conf = _conf("qwen2-0.5b.mirage")
    assert counts.gemm_params_per_layer(conf) == 14_909_440
    assert counts.gemm_params(conf) == 14_909_440 * 24 + 896 * 151_936


def test_train_flops_per_token():
    conf = _conf("qwen2-0.5b.mirage")
    attn = 3 * 24 * 2 * 2 * 256 * 14 * 64
    assert counts.train_flops_per_token(conf, 512) == pytest.approx(
        6 * counts.gemm_params(conf) + attn)


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.peaks("notes")


def test_peak_rule_by_policy():
    kind = "TPU v5 lite"
    assert counts.compute_peak(kind, {"mode": "mirage"}) == 393e12
    assert counts.compute_peak(kind, {"mode": "bf16"}) == 197e12
    assert counts.peaks(kind)["hbm_bytes_per_s"] == 819e9
