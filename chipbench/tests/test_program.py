"""The program is built as each configuration file and traffic mix state."""

import dataclasses
import json

import pytest

from chipbench import harness, program

CONFIGS = sorted((harness.HERE / "configs").glob("*.json"))
MIXES = sorted((harness.HERE / "traffic").glob("*.json"))

# every field of the ModelConfig the harness built from qwen2-0.5b's files
# before the program's own reader of published keys could stand in for it
QWEN2_0_5B = {
    "arch_id": "qwen2-0.5b", "family": "dense", "n_layers": 24,
    "d_model": 896, "n_heads": 14, "n_kv_heads": 2, "d_ff": 4864,
    "vocab_size": 151936, "head_dim": 64, "qkv_bias": True,
    "qk_norm": False, "tie_embeddings": True, "rope_theta": 1000000.0,
    "norm_eps": 1e-06, "norm_type": "rmsnorm", "sliding_window": None,
    "n_experts": 0, "experts_per_token": 0, "moe_d_ff": 0,
    "capacity_factor": 1.25, "router_aux_loss": 0.01, "ssm_state": 0,
    "ssm_headdim": 64, "ssm_expand": 2, "ssm_chunk": 256, "ssm_conv": 4,
    "ssm_groups": 1, "attn_every": 0, "encoder_layers": 0, "frontend": None,
    "frontend_dim": 0, "frontend_len": 0}
PINNED = {"qwen2-0.5b": QWEN2_0_5B}


@pytest.mark.parametrize("path", [
    p for p in CONFIGS if json.loads(p.read_text())["arch_id"] in PINNED],
    ids=lambda p: p.stem)
def test_model_config_is_the_files(path):
    conf = json.loads(path.read_text())
    got = dataclasses.asdict(program.model_config(conf))
    assert got == PINNED[conf["arch_id"]]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_traffic_keys_that_name_call_options_pass_through(path):
    from repro.models.lm import LMCallOptions

    conf = json.loads(CONFIGS[0].read_text())
    mix = json.loads(path.read_text())
    fields = {f.name for f in dataclasses.fields(LMCallOptions)}
    model = program.model(conf, program.policy(conf["policy"]), mix)
    want = LMCallOptions(**{k: v for k, v in mix.items() if k in fields})
    assert model.opt == want


def test_the_training_mix_sets_the_same_four_options():
    from repro.models.lm import LMCallOptions

    conf = json.loads(CONFIGS[0].read_text())
    mix = json.loads((harness.HERE / "traffic" / "train-b4s512.json")
                     .read_text())
    model = program.model(conf, program.policy(conf["policy"]), mix)
    assert model.opt == LMCallOptions(q_chunk=64, kv_chunk=64, remat=True,
                                      ce_chunk=512)


def test_a_new_call_option_in_a_mix_reaches_the_model():
    conf = json.loads(CONFIGS[0].read_text())
    mix = {"q_chunk": 32, "carry_dtype": "bfloat16", "batch": 4}
    model = program.model(conf, program.policy(conf["policy"]), mix)
    assert model.opt.q_chunk == 32 and model.opt.carry_dtype == "bfloat16"
