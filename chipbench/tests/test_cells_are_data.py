"""Every cell is data: its files are found by name, and no code names it."""

import json
import re

import pytest

from chipbench import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = harness.Cell(BENCH, name)
    assert cell.driver_path.is_file()
    assert cell.config["arch_id"]
    assert cell.end_to_end(), "every cell reports an end-to-end metric"
    names = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in names and len(names) >= 2
    layers = cell.per_layer()
    assert layers, "every cell reports a per-layer metric"
    for m in layers:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in names
    for k, v in cell.limits.items():
        assert k == "not_compared" or "limit" in v


def test_no_code_names_a_cell():
    for path in harness.HERE.rglob("*.py"):
        if "tests" in path.parts:
            continue
        text = path.read_text()
        for name in CELLS + [c["name"] for c in BENCH["configs"]]:
            assert name not in text, f"{path.name} names {name}"


def test_no_code_imports_a_reference():
    """A cell's reference is found by the name its configuration gives."""
    refs = {p.stem for p in (harness.HERE / "reference").glob("*.py")}
    for path in harness.HERE.rglob("*.py"):
        if {"tests", "reference"} & set(path.parts):
            continue
        text = path.read_text()
        assert "reference import" not in text, path.name
        for ref in refs:
            assert f"reference.{ref}" not in text, f"{path.name} names {ref}"


def test_cells_differ_only_in_data():
    """The bf16 training cell is the mirage one with another config file,
    and the two files differ only in their numerics."""
    w = {x["name"]: x for x in BENCH["workloads"]}
    a, b = w["train.qwen2-0.5b.mirage"], w["train.qwen2-0.5b.bf16"]
    assert a["traffic"] == b["traffic"] and a["chips"] == b["chips"]
    ca = harness.Cell(BENCH, a["name"]).config
    cb = harness.Cell(BENCH, b["name"]).config
    differ = {k for k in set(ca) | set(cb) if ca.get(k) != cb.get(k)}
    assert differ == {"policy", "precision", "control_policy", "control"}


def test_every_metric_file_and_name_is_well_formed():
    ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert ok.match(e["name"]), e["name"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["per_layer"]:
        for w in m.get("workloads", []):
            assert w in CELLS
