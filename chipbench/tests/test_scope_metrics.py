"""The per-layer metrics read from named scopes: device time by bucket.

A synthetic reduced trace (``reduce``'s ``ops``) and scope map stand for a
traced run; the rules of ``chipbench/scopes.py`` put each operation in one
bucket.
"""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, scopes

READERS = {"optimizer": "optimizer_ms_per_step.train",
           "gemm_quantize": "gemm_quantize_ms_per_step.train",
           "attention_core": "attention_core_ms_per_step.train",
           "layer_scan": "layer_scan_ms_per_step.train"}

J = "jit(train_step)"
LAYER = f"{J}/transpose(jvp(layers))/while/body/closed_call/checkpoint"
# instruction -> (op_name, seconds over the window); "gone" is not in the map
OPS = {
    "fusion.1": (f"{J}/optimizer/add", 0.010),
    "fusion.2": (f"{J}/clip/jit(clip)/reduce_sum", 0.002),
    "fusion.3": (f"{LAYER}/attn_qkv/gemm_dx/gemm/quantize_x/jit(round)/round",
                 0.030),
    "fusion.4": (f"{LAYER}/rematted_computation/mlp/gemm/quantize_w/abs",
                 0.020),
    "fusion.5": (f"{J}/jvp(layers)/while/body/closed_call/attn_core/"
                 "while/body/exp", 0.016),
    "convolution.6": (f"{LAYER}/attn_core/while/body/dot_general", 0.008),
    "copy.7": (f"{J}/transpose(jvp(layers))/while/body/dynamic_slice",
               0.004),
    "fusion.8": (f"{LAYER}/mlp/gemm_dw/gemm/matmul/dot_general", 0.040),
    "fusion.9": (f"{J}/transpose(jvp(head_ce))/while/body/closed_call/"
                 "jit(log_softmax)/sub", 0.006),
    "copy.10": ("", 0.001),
    "fusion.11": (f"{J}/jit(clip)/max", 0.003),
    "gone.12": (None, 0.002),
}
STEPS = 2


def _run(scope_map=True, steps=STEPS):
    ops = {f"jit_train_step/{k}": {"s": s, "n": STEPS, "class": "other"}
           for k, (_, s) in OPS.items()}
    ops["jit_other/fusion.1"] = {"s": 0.005, "n": 1, "class": "other"}
    m = {"program": "jit_train_step", "seconds": 0.0,
         "scopes": {k: n for k, (n, _) in OPS.items() if n is not None}}
    # a program without op_scopes leaves None in the run
    layer = {"trace": {"ops": ops}, "steps": steps,
             "op_scopes": m if scope_map else None}
    return types.SimpleNamespace(layer=layer)


def test_buckets_partition_the_ops():
    run = _run()
    split = scopes.split(run)
    total = sum(o["s"] for o in run.layer["trace"]["ops"].values()) / STEPS
    assert sum(split[b] for b in scopes.BUCKETS) == pytest.approx(total)
    assert split["optimizer"] == pytest.approx(0.012 / STEPS)
    assert split["gemm_quantize"] == pytest.approx(0.050 / STEPS)
    assert split["attention_core"] == pytest.approx(0.024 / STEPS)
    assert split["layer_scan"] == pytest.approx(0.004 / STEPS)
    assert split["other_scoped"] == pytest.approx(0.046 / STEPS)
    # no scope, missing from the map, or another program's instruction
    assert split["unscoped"] == pytest.approx(0.011 / STEPS)
    assert split["missing"] == pytest.approx(0.007 / STEPS)


@pytest.mark.parametrize("op_name,want", [
    # a quantize op inside attn_qkv counts as quantize
    (f"{LAYER}/attn_qkv/gemm/quantize_w/mul", "gemm_quantize"),
    # the optimizer rule comes first
    (f"{J}/optimizer/quantize_x/mul", "optimizer"),
    # quantize before attention, attention before the scan
    (f"{LAYER}/attn_core/gemm/quantize_x/mul", "gemm_quantize"),
    (f"{J}/jvp(layers)/while/body/attn_core/exp", "attention_core"),
    (f"{J}/jvp(layers)/while/body/dynamic_update_slice", "layer_scan"),
    (f"{J}/jvp(layers)/while/body/attn_out/add", "other_scoped"),
    (f"{J}/jvp(layers)/while/body/gemm/matmul/dot_general", "other_scoped"),
    # a jitted function named like a scope is no scope
    (f"{J}/jit(clip)/max", "unscoped"),
    ("state['params']['layers']['mlp']['up']['w']", "unscoped"),
    # XLA joins the names of instructions it merges with ";"
    ("reshape;attn_qkv", "other_scoped"),
])
def test_rule_order(op_name, want):
    assert scopes.bucket(op_name) == want


@pytest.mark.parametrize("bucket", sorted(READERS))
def test_readers_read_their_bucket(bucket):
    reader = harness.load_module(
        harness.HERE / "metrics" / f"{READERS[bucket]}.py")
    run = _run()
    assert reader.read(run) == pytest.approx(1e3 * scopes.split(run)[bucket])


@pytest.mark.parametrize("bucket", sorted(READERS))
def test_readers_return_none_without_a_map_or_trace(bucket):
    reader = harness.load_module(
        harness.HERE / "metrics" / f"{READERS[bucket]}.py")
    assert reader.read(_run(scope_map=False)) is None
    assert reader.read(_run(steps=0)) is None
    run = _run()
    del run.layer["trace"]
    assert reader.read(run) is None
    # a program without named scopes (one whose step predates them, or an
    # executable compiled under other names): no bucket to read
    run = _run()
    run.layer["op_scopes"]["scopes"] = dict.fromkeys(
        run.layer["op_scopes"]["scopes"], f"{J}/add")
    assert reader.read(run) is None


def test_align_keys_the_scopes_by_the_names_that_ran():
    """Metadata can renumber instructions: the names of the executable
    that ran get the scopes of the same instructions in the fresh compile,
    matched by position."""
    ran = ("HloModule jit_f\n"
           "  %p = f32[8]{0} parameter(0), metadata={op_name=\"x\"}\n"
           "  %reshape.2279 = f32[2,4]{1,0} reshape(%p)\n"
           "  ROOT %fusion.1 = f32[2,4]{1,0} fusion(%reshape.2279), "
           "kind=kLoop, calls=%fc.1, metadata={op_name=\"jit(f)/add\"}\n")
    fresh = (ran.replace("reshape.2279", "reshape.2297")
             .replace("jit(f)/add", "jit(f)/optimizer/add"))
    names = {"p": "x", "reshape.2297": "jit(f)/optimizer/reshape",
             "fusion.1": "jit(f)/optimizer/add"}
    got = scopes.align(ran, fresh, names)
    assert got == {"p": "x", "reshape.2279": "jit(f)/optimizer/reshape",
                   "fusion.1": "jit(f)/optimizer/add"}
    # another program: matched by name
    other = ran.replace("f32[2,4]", "f32[4,2]")
    assert scopes.align(other, fresh, names) == names


def test_scope_map_of_a_cell(tmp_path):
    """The tiny copy's mirage step, compiled again after a run: every GEMM
    phase and the optimizer are named, and the map is kept in the run."""
    from chipbench.tests import tiny

    bench = tiny.bench_copy(tmp_path)
    cell = harness.Cell(bench, "train.qwen2-0.5b.mirage", root=tmp_path,
                        base=tmp_path)
    run = types.SimpleNamespace(cell=cell, program_policy=None, layer={})
    m = scopes.scope_map(run)
    assert m["program"] == "jit_train_step" and m["seconds"] > 0
    assert scopes.scope_map(run) is m is run.layer["op_scopes"]
    buckets = {scopes.bucket(n) for n in m["scopes"].values()}
    assert {"optimizer", "gemm_quantize", "attention_core",
            "layer_scan"} <= buckets


def test_named_compile_keys_the_cache_on_metadata_and_restores():
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    with scopes.named_compile(True):
        assert getattr(jax.config, key) is True
    assert getattr(jax.config, key) == was
    with scopes.named_compile(False):
        assert getattr(jax.config, key) == was


_COMPILE = """
import sys, jax, jax.numpy as jnp
from chipbench import scopes
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
scoped, named = sys.argv[2] == "1", sys.argv[3] == "1"

def f(x):
    if not scoped:
        return jnp.sin(x) * 2 + 1
    with jax.named_scope("optimizer"):
        return jnp.sin(x) * 2 + 1

with scopes.named_compile(named):
    c = jax.jit(f).lower(jnp.ones(256)).compile()
print("optimizer" in c.as_text())
"""


def test_named_compile_reads_fresh_names_through_a_warm_cache(tmp_path):
    """A named scope changes only metadata, and the persistent cache keys a
    program with its metadata stripped: after the program without scopes
    is cached, the scoped one comes back under the old names, unless the
    compile keys the cache on metadata (``named_compile``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT),
                                           str(harness.ROOT / "src")]))

    def compiled_names(scoped, named):
        out = subprocess.run(
            [sys.executable, "-c", _COMPILE, str(tmp_path / "cache"),
             str(int(scoped)), str(int(named))],
            env=env, capture_output=True, text=True, timeout=300, check=True)
        return out.stdout.split()[-1] == "True"

    assert not compiled_names(scoped=False, named=False)
    assert not compiled_names(scoped=True, named=False)   # stale names
    assert compiled_names(scoped=True, named=True)
