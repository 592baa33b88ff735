"""A configuration of another architecture joins the benchmark as new files.

In a temporary directory, a configuration names a reference module that
only this test has, beside its traffic mix and limits. The harness resolves
them by name and runs the cell on the CPU, taking the count of work from
that module; a reference whose parameter tree differs from the program's
by one leaf stops the run before its first step. No file under
``chipbench/`` changes.
"""

import hashlib
import json

import pytest

from chipbench import harness
from chipbench.tests import tiny

FLOPS = 1234567.0
PROBE = f'''
"""A test-only reference: qwen2's, with a count of its own."""
from chipbench.reference.qwen2 import (  # noqa: F401
    leaf_norms, param_shapes, train_readings)


def train_flops_per_token(conf, seq):
    return {FLOPS!r}
'''
# the same, with the final norm's scale one wider than the program's
OFF_BY_ONE_LEAF = PROBE + '''

def param_shapes(conf):
    from chipbench.reference import qwen2

    shapes = dict(qwen2.param_shapes(conf))
    shapes["final_norm/scale"] = (conf["hidden_size"] + 1,)
    return shapes
'''
CONF = {"arch_id": "qwen2-0.5b", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
        "tie_word_embeddings": True, "policy": {"mode": "bf16"},
        "attention_operands": "fp32", "control_policy": {"mode": "int8"}}
MIX = {"kind": "train", "batch": 2, "seq": 64, "optimizer": "adamw",
       "lr": 0.001, "grad_clip": 1.0, "remat": True, "ce_chunk": 64,
       "q_chunk": 32, "kv_chunk": 32, "check_steps": 3, "trace_seconds": 1}
LIMITS = harness.HERE / "limits" / "qwen2-0.5b.bf16.train-b4s512.json"


def _tree():
    return {p.relative_to(harness.HERE): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in harness.HERE.rglob("*")
        if p.is_file() and "__pycache__" not in p.parts}


def _add(tmp, reference):
    """The new configuration's files under ``tmp``, and its BENCHMARK."""
    for sub in ("configs", "traffic", "limits", "reference"):
        (tmp / sub).mkdir(parents=True, exist_ok=True)
    (tmp / "reference" / "probe.py").write_text(PROBE)
    (tmp / "reference" / "probe_off.py").write_text(OFF_BY_ONE_LEAF)
    (tmp / "configs" / "probe-arch.json").write_text(
        json.dumps(dict(CONF, reference=reference)))
    (tmp / "traffic" / "probe-train.json").write_text(json.dumps(MIX))
    (tmp / "limits" / "probe-arch.probe-train.json").write_text(
        LIMITS.read_text())
    cell = "train.probe-arch"
    return {
        "configs": [{"name": "probe-arch",
                     "file": "configs/probe-arch.json"}],
        "workloads": [{"name": cell, "config": "probe-arch",
                       "traffic": "probe-train", "chips": 1}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "workloads": [cell]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "step_mfu.train", "unit": "%",
                       "moves": "train_tokens_per_s", "workloads": [cell]}],
    }


def _cell(tmp, bench):
    return harness.Cell(bench, "train.probe-arch", root=tmp, base=tmp)


def test_a_new_reference_runs_as_new_files(tmp_path):
    before = _tree()
    bench = _add(tmp_path, "probe")
    cell = _cell(tmp_path, bench)
    assert cell.reference.train_flops_per_token(CONF, 64) == FLOPS
    run = harness.execute(cell, 2**31 + 11, 0.5, False, 0.0,
                          device_check=tiny.no_chip)
    assert run.layer["flops_per_token"] == FLOPS
    assert run.correct, run.checks
    line = harness.result_line(run)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert _tree() == before


def test_a_tree_that_differs_by_one_leaf_stops_before_any_step(
        tmp_path, monkeypatch):
    from repro.runtime import trainer

    before = _tree()
    built = []
    make = trainer.make_train_step
    monkeypatch.setattr(trainer, "make_train_step",
                        lambda *a, **k: built.append(1) or make(*a, **k))
    cell = _cell(tmp_path, _add(tmp_path, "probe_off"))
    with pytest.raises(harness.BenchError, match="final_norm/scale"):
        harness.execute(cell, 2**31 + 12, 0.5, False, 0.0,
                        device_check=tiny.no_chip)
    assert built == []
    assert _tree() == before


def test_a_missing_reference_is_a_bench_error(tmp_path):
    bench = _add(tmp_path, "nowhere")
    with pytest.raises(harness.BenchError, match="nowhere"):
        _cell(tmp_path, bench)
