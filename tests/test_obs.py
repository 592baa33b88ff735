"""Unified telemetry (repro.obs): metrics registry, span tracer, health.

Covers the observability PR's acceptance surface: label-cardinality guard,
histogram bucket-edge semantics, ring-buffer wraparound, snapshot-while-
writing thread safety, and RRNS fault-counter parity against the frozen
``rrns_decode_np`` host oracle on injected single/double residue errors.
"""

import json
import re
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analog import rrns
from repro.core import noise
from repro.core.precision import get_policy, special_moduli
from repro.obs import health
from repro.obs.metrics import (DEFAULT_BUCKETS, MAX_LABEL_SETS,
                               MetricsRegistry)
from repro.obs.trace import SpanTracer


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2)
    g = reg.gauge("depth", "queue depth")
    g.set(5)
    g.dec(2)
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["req_total"]["series"]["_"] == 3
    assert snap["depth"]["series"]["_"] == 3
    hs = snap["lat_s"]["series"]["_"]
    assert hs["count"] == 3 and hs["counts"] == [1, 1, 1]
    assert abs(hs["sum"] - 5.55) < 1e-9
    # get-or-create is idempotent; kind mismatch is always a bug
    assert reg.counter("req_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("req_total")


def test_labels_resolve_and_cardinality_guard_trips():
    reg = MetricsRegistry()
    c = reg.counter("flips", label_names=("channel",))
    c.labels("31").inc(4)
    c.labels(31).inc(1)          # values stringify: same child
    assert c.labels("31").value == 5
    with pytest.raises(ValueError, match="expected 1 label"):
        c.labels("31", "32")
    for i in range(MAX_LABEL_SETS - 1):
        c.labels(f"m{i}").inc()
    with pytest.raises(ValueError, match="cardinality"):
        c.labels("one-too-many")


def test_histogram_bucket_edges_are_le_upper_bounds():
    """A value exactly ON an edge lands in that edge's bucket (Prometheus
    cumulative ``le`` semantics); past the last edge goes to +Inf."""
    reg = MetricsRegistry()
    h = reg.histogram("edges", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 2.0, 2.0001, 4.0, 99.0):
        h.observe(v)
    snap = reg.snapshot()["edges"]["series"]["_"]
    assert snap["counts"] == [2, 1, 2, 1]      # le=1, le=2, le=4, +Inf
    text = reg.prometheus_text()
    assert 'edges_bucket{le="1"} 2' in text
    assert 'edges_bucket{le="2"} 3' in text    # cumulative
    assert 'edges_bucket{le="4"} 5' in text
    assert 'edges_bucket{le="+Inf"} 6' in text
    assert "edges_count 6" in text
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("bad", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("bad2", buckets=(2.0, 1.0))


def test_histogram_percentile_interpolates_within_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("p", buckets=(10.0, 20.0))
    for _ in range(100):
        h.observe(15.0)          # all in (10, 20]
    assert 10.0 <= h.percentile(0.5) <= 20.0
    assert h.percentile(0.0) == 0.0 or h.percentile(0.0) <= 20.0
    empty = reg.histogram("p0", buckets=(1.0,))
    assert empty.percentile(0.99) == 0.0


def test_prometheus_text_parses():
    """Every exposition line matches the text-format grammar a scraper
    (and the CI smoke) expects."""
    reg = MetricsRegistry()
    reg.counter("a_total", "things").inc()
    reg.gauge("b", label_names=("ch",)).labels("31").set(2)
    reg.histogram("c_s", "lat", buckets=DEFAULT_BUCKETS[:3]).observe(0.002)
    line_re = re.compile(
        r'^(# (HELP|TYPE) \S.*'
        r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_]+="[^"]*"'
        r'(,[a-zA-Z_]+="[^"]*")*\})? (\+Inf|-?[0-9.e+-]+))$')
    text = reg.prometheus_text()
    for ln in text.splitlines():
        assert line_re.match(ln), f"malformed exposition line: {ln!r}"
    assert text.endswith("\n")


def test_gauge_fn_and_collectors_run_at_scrape_time():
    reg = MetricsRegistry()
    box = {"v": 1.0}
    reg.gauge_fn("lazy", lambda: box["v"])
    calls = []
    reg.add_collector(lambda r: calls.append(1) or
                      r.gauge("collected").set(7))
    box["v"] = 42.0
    snap = reg.snapshot()
    assert snap["lazy"]["series"]["_"] == 42.0
    assert snap["collected"]["series"]["_"] == 7
    assert len(calls) == 1                      # once per scrape

    def broken(r):
        raise RuntimeError("boom")
    reg.add_collector(broken)
    reg.snapshot()                              # never kills a scrape


def test_snapshot_while_writing_is_consistent():
    """Scrapes racing writer threads must never see a torn histogram
    (sum(counts) != count) and final totals must be exact."""
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("lat", buckets=(0.5, 1.0, 2.0))
    n_threads, n_each = 4, 2000
    start = threading.Barrier(n_threads + 1)

    def writer(seed):
        rng = np.random.default_rng(seed)
        vals = rng.uniform(0, 3, n_each)
        start.wait()
        for v in vals:
            c.inc()
            h.observe(float(v))

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    start.wait()
    torn = 0
    for _ in range(200):
        hs = reg.snapshot()["lat"]["series"]["_"]
        if sum(hs["counts"]) != hs["count"]:
            torn += 1
        reg.prometheus_text()
    for t in threads:
        t.join()
    assert torn == 0
    assert c.value == n_threads * n_each
    assert h.count == n_threads * n_each


# --------------------------------------------------------------------------
# span tracer
# --------------------------------------------------------------------------

def test_disabled_tracer_records_nothing_and_reuses_null_cm():
    tr = SpanTracer(capacity=4, enabled=False)
    cm1 = tr.span("a")
    cm2 = tr.span("b")
    assert cm1 is cm2            # the shared no-op context manager
    with cm1:
        pass
    tr.instant("mark")
    assert tr.n_recorded == 0 and tr.spans() == []


def test_ring_wraparound_keeps_most_recent_spans():
    tr = SpanTracer(capacity=8, enabled=True)
    for i in range(20):
        with tr.span(f"s{i}", {"i": i}):
            pass
    assert tr.n_recorded == 20
    assert tr.n_dropped == 12
    got = [s["name"] for s in tr.spans()]
    assert got == [f"s{i}" for i in range(12, 20)]   # oldest first
    # chrome trace is valid JSON with one event per surviving span
    doc = json.loads(json.dumps(tr.chrome_trace()))
    assert len(doc["traceEvents"]) == 8
    assert doc["otherData"]["dropped_spans"] == 12
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X" and ev["dur"] >= 0
    tr.clear()
    assert tr.n_recorded == 0 and tr.spans() == []
    with pytest.raises(ValueError):
        SpanTracer(capacity=0)


def test_tracer_export_and_thread_safety(tmp_path):
    tr = SpanTracer(capacity=64, enabled=True)
    done = threading.Barrier(5)

    def worker():
        done.wait()
        for _ in range(100):
            with tr.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    done.wait()
    for _ in range(50):
        tr.spans()               # concurrent reads during writes
    for t in threads:
        t.join()
    assert tr.n_recorded == 400
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert len(doc["traceEvents"]) == 64        # capacity-bounded


# --------------------------------------------------------------------------
# analog-health counters vs the frozen RRNS oracle
# --------------------------------------------------------------------------

BASE = list(special_moduli(5))
ALL = BASE + list(rrns.default_redundant_moduli(5))
PSI = (int(np.prod(BASE)) - 1) // 2


def _residues(xs):
    return np.stack([np.mod(xs, m) for m in ALL]).astype(np.int32)


def _decode_with_health(res, tables):
    # eager (not jitted): the recorded values must be CONCRETE so the test
    # can read them off the collector directly; the serving engine's jitted
    # steps instead fold them into device accumulators (see obs/health.py)
    with health.collect() as hc:
        dec, cor = rrns.rrns_decode(jnp.asarray(res), tables)
    return (np.asarray(dec), np.asarray(cor),
            {k: np.asarray(v) for k, v in hc.values.items()})


def test_health_counters_match_oracle_on_single_residue_errors():
    """Every single-residue error is repairable with r=2: the counters
    must report exactly the oracle's corrected-flag count, zero
    uncorrected, and the decode itself must bit-match the oracle."""
    rng = np.random.default_rng(3)
    xs = rng.integers(1, PSI + 1, size=128)     # nonzero ground truth
    res = _residues(xs)
    hit = rng.random(128) < 0.5
    pos = rng.integers(0, len(ALL), size=128)
    for j in np.flatnonzero(hit):
        m = ALL[pos[j]]
        res[pos[j], j] = (res[pos[j], j] + rng.integers(1, m)) % m
    tables = rrns.build_tables(ALL, 3, PSI)
    dec, cor, h = _decode_with_health(res, tables)
    dec_np, cor_np = noise.rrns_decode_np(res.astype(np.int64), ALL, 3, PSI)
    np.testing.assert_array_equal(dec, dec_np)
    np.testing.assert_array_equal(cor, cor_np)
    np.testing.assert_array_equal(dec, xs)      # all repaired to truth
    assert int(h["rrns_corrected"]) == int(cor_np.sum()) > 0
    assert int(h["rrns_uncorrected"]) == 0


def test_health_counters_match_oracle_on_double_residue_errors():
    """Two simultaneous residue errors exceed the r=2 correction radius:
    the corrected/uncorrected split must still partition the oracle's
    flagged positions exactly (flagged = repaired ∪ unrepairable), and
    unrepairable positions are exactly the oracle's clamped-to-0 ones."""
    rng = np.random.default_rng(4)
    xs = rng.integers(1, PSI + 1, size=96)      # nonzero ground truth
    res = _residues(xs)
    for j in range(0, 96, 2):                   # half get double errors
        p = int(rng.integers(0, len(ALL)))
        q = (p + 1 + int(rng.integers(0, len(ALL) - 1))) % len(ALL)
        for k in (p, q):
            m = ALL[k]
            res[k, j] = (res[k, j] + rng.integers(1, m)) % m
    tables = rrns.build_tables(ALL, 3, PSI)
    dec, cor, h = _decode_with_health(res, tables)
    dec_np, cor_np = noise.rrns_decode_np(res.astype(np.int64), ALL, 3, PSI)
    np.testing.assert_array_equal(dec, dec_np)
    np.testing.assert_array_equal(cor, cor_np)
    n_corr, n_unc = int(h["rrns_corrected"]), int(h["rrns_uncorrected"])
    assert n_corr + n_unc == int(cor_np.sum()) > 0
    # the split follows the correction-radius certificate: a trustworthy
    # winner agrees with >= n_total - floor(r/2) = 4 moduli (single-error
    # radius for r=2). Double-error elements fall below it even when some
    # legal — wrong — value wins the vote (legality alone certifies
    # nothing: the all-base subset is legal for every residue tuple)
    cons = np.stack([dec_np % m == res[i] % m
                     for i, m in enumerate(ALL)]).sum(axis=0)
    assert n_unc == int((cons < len(ALL) - 1).sum()) > 0


def test_health_counters_zero_on_clean_residues():
    xs = np.arange(1, 65)
    tables = rrns.build_tables(ALL, 3, PSI)
    dec, cor, h = _decode_with_health(_residues(xs), tables)
    np.testing.assert_array_equal(dec, xs)
    assert int(h["rrns_corrected"]) == 0
    assert int(h["rrns_uncorrected"]) == 0


def test_record_is_noop_without_scope_and_under_suppression():
    health.record("rrns_corrected", jnp.ones(()))   # no scope: no-op
    with health.collect() as hc:
        with health.suppressed():
            assert not health.active()
            health.record("rrns_corrected", jnp.ones(()))
        assert health.active()
        health.record("rrns_corrected", jnp.asarray(2, jnp.int32))
    assert int(hc.values["rrns_corrected"]) == 2


def test_lifted_scan_reraises_inner_records_one_level_up():
    """Records inside a scan body cross to the enclosing scope via the
    lift (stacked outputs summed over the scan axis) — composing through
    a nested scan."""
    def inner_body(c, x):
        health.record("hits", jnp.asarray(1, jnp.int32))
        return c + x, x

    def outer_body(c, x):
        s, _ = health.lifting_scan(health.lifted(inner_body),
                                   jnp.zeros(()), jnp.ones((3,)) * x)
        return c + s, s

    with health.collect() as hc:
        total, _ = health.lifting_scan(health.lifted(outer_body),
                                       jnp.zeros(()), jnp.ones((4,)))
    assert float(total) == 12.0
    assert int(hc.values["hits"]) == 12


def test_spec_and_fold_contract():
    assert health.spec(get_policy("mirage")) == {}
    s = health.spec(get_policy("mirage_rrns"))
    assert set(s) == {"rrns_corrected", "rrns_uncorrected"}
    sn = health.spec(get_policy("mirage_rrns", snr_db=12.0, noise_seed=0))
    assert "detector_flips" in sn and sn["detector_flips"][0] == len(ALL)
    acc = health.init(s)
    acc2 = health.fold(acc, {"rrns_corrected": jnp.asarray(3, jnp.int32),
                             "not_in_spec": jnp.asarray(9, jnp.int32)})
    assert int(acc2["rrns_corrected"]) == 3
    assert int(acc2["rrns_uncorrected"]) == 0
    assert "not_in_spec" not in acc2            # spec is the contract


# --------------------------------------------------------------------------
# named scopes: the compiled train step's device work by program layer
# --------------------------------------------------------------------------

# the scopes the program opens (repro.obs.trace module docstring)
SCOPES = {"gemm", "gemm_dx", "gemm_dw", "quantize_x", "quantize_w", "matmul",
          "prequantize", "embed", "layers", "attn_norm", "attn_qkv",
          "attn_core", "attn_out", "mlp_norm", "mlp", "final_norm", "head_ce",
          "clip", "optimizer"}


def _scopes_on(op_name):
    """Scope names on an op_name path, transforms (jvp, transpose)
    unwrapped; a jitted function's segment (``jit(clip)``) is no scope."""
    found = set()
    for seg in re.split(r"[/;]", op_name):
        m = re.match(r"^(?:jvp|transpose|vmap)\((.*)\)$", seg)
        while m:
            seg = m.group(1)
            m = re.match(r"^(?:jvp|transpose|vmap)\((.*)\)$", seg)
        if seg in SCOPES:
            found.add(seg)
    return found


@pytest.fixture(scope="module", params=["mirage", "bf16"])
def step_hlo(request):
    """The optimized HLO of the benchmark's train step at qwen2-0.5b's
    published widths, cut to 2 layers and a 4,096-token vocabulary, batch
    4 x 512, layer remat and 512-token CE chunks."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.models import build_model
    from repro.models.lm import LMCallOptions
    from repro.runtime.trainer import init_train_state, make_train_step

    cfg = dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                              vocab_size=4096, norm_eps=1e-6)
    extra = {"b_m": 4, "g": 16} if request.param == "mirage" else {}
    pol = get_policy(request.param, **extra)
    model = build_model(cfg, pol, LMCallOptions(
        remat=True, ce_chunk=512, q_chunk=64, kv_chunk=64))
    tc = TrainConfig(policy=pol, optimizer="adamw", lr=1e-3, grad_clip=1.0)
    state = jax.eval_shape(lambda k: init_train_state(model, tc, k),
                           jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((4, 512), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(model, tc), donate_argnums=0)
    return request.param, step.lower(state, batch).compile().as_text()


def _top_level(hlo):
    """Instructions of the entry computation and of every while body,
    transitively: the operations a profiler trace shows."""
    from repro.launch.hlo_analysis import parse_computations

    comps = parse_computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo, re.M).group(1)
    todo, seen = [entry], set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for ins in comps[name].instrs:
            if ins.op == "while":
                todo.append(re.search(r"\bbody=%?([\w.\-]+)",
                                      ins.rhs).group(1))
    return [ins for n in seen for ins in comps[n].instrs]


def test_op_scopes_name_every_gemm_phase(step_hlo):
    from repro.obs.trace import op_scopes

    mode, hlo = step_hlo
    names = list(op_scopes(hlo).values())
    for scope in ("attn_core", "optimizer", "clip", "layers", "head_ce"):
        assert any(scope in _scopes_on(n) for n in names), scope
    if mode != "mirage":    # a bf16 cast fuses into its user
        return
    quant = [n for n in names if _scopes_on(n) & {"quantize_x", "quantize_w"}]
    forward = [n for n in quant if "transpose(" not in n]
    remat = [n for n in quant if "rematted_computation" in n]
    for phase in (forward, remat,
                  [n for n in quant if "gemm_dx" in _scopes_on(n)],
                  [n for n in quant if "gemm_dw" in _scopes_on(n)]):
        for scope in ("quantize_x", "quantize_w"):
            assert any(scope in _scopes_on(n) for n in phase), (
                scope, len(phase))


def test_every_top_level_fusion_and_matmul_is_scoped(step_hlo):
    from repro.obs.trace import op_scopes

    _, hlo = step_hlo
    m = op_scopes(hlo)
    top = _top_level(hlo)
    const = {i.name for i in top if i.op == "constant"}
    # a fill of constants reads no data: the zero JAX instantiates for the
    # CE's transposed ``where`` carries no scope, and XLA on the TPU fuses
    # it into its user
    work = [i for i in top if i.op in ("fusion", "dot", "convolution")
            and not set(i.operands) <= const]
    assert len(work) > 20
    bare = [(i.name, m.get(i.name)) for i in work
            if not _scopes_on(m.get(i.name) or "")]
    assert not bare, bare[:10]


def test_op_scopes_reads_top_level_instructions_only():
    from repro.obs.trace import op_scopes

    def f(x):
        with jax.named_scope("optimizer"):
            y = jnp.sin(x) * 2 + 1

        def body(c, _):
            with jax.named_scope("attn_core"):
                return c * 1.5 + jnp.cos(c), None
        with jax.named_scope("layers"):
            return jax.lax.scan(body, y, None, length=3)[0]

    hlo = jax.jit(f).lower(jnp.ones(64)).compile().as_text()
    m = op_scopes(hlo)
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    body = re.search(r"\bbody=%?([\w.\-]+)", hlo).group(1)
    from repro.launch.hlo_analysis import parse_computations
    comps = parse_computations(hlo)
    for name in fused:
        assert not any(i.name in m for i in comps[name].instrs)
    assert all(i.name in m for i in comps[body].instrs)
    assert any("attn_core" in _scopes_on(m[i.name])
               for i in comps[body].instrs)
    assert any("optimizer" in _scopes_on(n) for n in m.values())
    # an instruction without metadata takes its user's or its caller's
    assert all(m[i.name] for i in comps[body].instrs)
