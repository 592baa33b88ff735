"""Counts of work and the table of peaks."""

import json

import jax
import numpy as np
import pytest

from chipbench import counts, harness, program

CONFIGS = sorted((harness.HERE / "configs").glob("*.json"))
QWEN2 = [p for p in CONFIGS
         if json.loads(p.read_text())["reference"] == "qwen2"]


def _conf(name):
    return json.loads((harness.HERE / "configs" / f"{name}.json").read_text())


def _reference(conf):
    return harness.load_module(
        harness.HERE / "reference" / f"{conf['reference']}.py")


def _program_shapes(conf):
    """``{leaf name: shape}`` of the program's parameters for ``conf``."""
    model = program.model(conf, program.policy(conf["policy"]), {})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {"/".join(str(getattr(k, "key", k)) for k in p): x.shape
            for p, x in flat}


def _matmul_weights(shapes):
    # matmul weights; emb is the head
    return sum(int(np.prod(s)) for k, s in shapes.items()
               if k.rsplit("/", 1)[-1] in ("w", "emb"))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_gemm_params_match_the_models_matmul_weights(path):
    """The program's matmul weights are the ones the file's own reference
    states, whatever its architecture."""
    conf = json.loads(path.read_text())
    want = _reference(conf).param_shapes(conf)
    assert _matmul_weights(_program_shapes(conf)) == _matmul_weights(want)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_program_tree_is_the_references_at_full_width(path):
    """The driver's guard passes on every file as it is run."""
    conf = json.loads(path.read_text())
    driver = harness.load_module(harness.HERE / "drivers" / "train.py")
    model = program.model(conf, program.policy(conf["policy"]), {})
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    driver.check_tree(params, _reference(conf).param_shapes(conf))


@pytest.mark.parametrize("path", QWEN2, ids=lambda p: p.stem)
def test_qwen2_reference_counts_the_dense_decoder(path):
    """The qwen2 reference's count is ``counts.py``'s dense formula, bit for
    bit, over the weights its tree holds."""
    conf = json.loads(path.read_text())
    ref = _reference(conf)
    assert ref.train_flops_per_token(conf, 512) == \
        counts.train_flops_per_token(conf, 512)
    assert counts.gemm_params(conf) == _matmul_weights(
        ref.param_shapes(conf))


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
