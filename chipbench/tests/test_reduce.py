"""The reduction from a profiler trace to busy time, ops and idle gaps.

``data/train_trace.xplane.pb.xz`` is a trace recorded on one v5e chip by
``calibrate.py --workload train.qwen2-0.5b.mirage --keep-trace DIR
--seconds 0.35``: two steps of the trainer's step program, xz-compressed.
"""

import lzma
from pathlib import Path

import pytest

from chipbench import reduce

DATA = Path(__file__).resolve().parent / "data" / "train_trace.xplane.pb.xz"


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "train.xplane.pb"
    path.write_bytes(lzma.decompress(DATA.read_bytes()))
    return reduce.reduce_file(path)


def test_busy_plus_idle_is_the_window(red):
    idle = sum(g["s"] for g in red["gaps"])
    assert red["window_s"] > 0 and red["busy_s"] > 0
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-9)


def test_matmul_and_other_cover_every_op_once(red):
    mm, other = reduce.matmul_seconds(red)
    assert mm > 0 and other > 0
    # self times of nested ops add up to the union of the device's busy
    # time, but for the few microseconds by which async copies overlap
    assert mm + other == pytest.approx(red["busy_s"], rel=1e-5)
    assert {o["class"] for o in red["ops"].values()} == {"matmul", "other"}


def test_the_step_program_is_found(red):
    progs = red["programs"]
    assert "jit_train_step" in progs and progs["jit_train_step"]["n"] >= 1
    assert sum(p["s"] for p in progs.values()) <= red["window_s"]


def test_gaps_are_attributed_to_the_covering_host_span(red):
    spans = {g["span"] for g in red["gaps"]}
    assert spans <= {"train.step", "train.data_next", "train.host_sync",
                     "(none)"}
    # the device waits for the host between steps: some idle time falls
    # inside a train.* span
    assert any(s.startswith("train.") for s in spans)


def test_breakdown_shape(red):
    b = reduce.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_union_and_self_times():
    assert reduce._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    evs, st = reduce._self_times([(0, 10, "while"), (1, 4, "a"),
                                  (5, 7, "b"), (12, 13, "c")])
    assert dict(zip([e[2] for e in evs], st)) == {
        "while": 5, "a": 3, "b": 2, "c": 1}


def test_op_class():
    assert reduce.op_class(
        "%fusion.3 = f32[8,8]{1,0} fusion(f32[8,8] %a), kind=kOutput, "
        "calls=%fused_computation.3") == "matmul"
    assert reduce.op_class(
        "%convolution.1 = f32[8,8]{1,0} convolution(f32[8,8] %a, "
        "f32[8,8] %b), dim_labels=bf_io->bf") == "matmul"
    assert reduce.op_class(
        "%fusion.4 = f32[8]{0} fusion(f32[8,8] %a), kind=kLoop, "
        "calls=%fused_computation.4") == "other"
