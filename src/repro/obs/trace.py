"""Span tracer: a thread-safe ring buffer of timed spans, ~zero cost off,
and the map from a compiled program's device operations to named scopes.

The serving tick pipeline (admit → prefill chunk → draft → verify/decode →
host sync), the bucketed prefill and trainer steps are instrumented with
:meth:`SpanTracer.span`. Design:

  * **disabled is the default and costs one attribute check**: ``span()``
    on a disabled tracer returns a shared no-op context manager — no
    generator frame, no clock read, no allocation.
  * **bounded memory**: spans land in a preallocated ring buffer
    (``capacity`` spans, default 64k); wraparound keeps the most recent
    spans. A long soak never grows the tracer.
  * **Chrome-trace export**: :meth:`chrome_trace` renders the ring as a
    ``traceEvents`` JSON object (``ph: "X"`` complete events, microsecond
    timestamps) loadable in ``chrome://tracing`` / Perfetto;
    :meth:`export` writes it to a file (``launch/serve.py
    --trace-export``).
  * **jax.profiler composition**: with ``annotate=True`` every span also
    opens a ``jax.profiler.TraceAnnotation``, so host spans line up with
    XLA device activity inside a profiler capture window
    (``launch/serve.py --profile-window`` wraps N ticks in
    ``jax.profiler.trace``). Spans are host-side only: code traced inside
    ``jax.jit`` runs once, at trace time, so a span there names no device
    operation.

Device-side names come from ``jax.named_scope``: the GEMM backend's phases
(``gemm``, ``gemm_dx``, ``gemm_dw``, ``quantize_x``, ``quantize_w``,
``matmul``, ``prequantize``), the model's sub-layers (``embed``,
``layers``, ``attn_norm``, ``attn_qkv``, ``attn_core``, ``attn_out``,
``mlp_norm``, ``mlp``, ``final_norm``, ``head_ce``) and the train step's
``clip`` and ``optimizer``. A scope goes into each HLO instruction's
``op_name`` metadata at no run-time cost; :func:`op_scopes` reads it back
from the compiled program, keyed by the instruction names a profiler trace
gives its device operations.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
from typing import Dict, List, Optional

_NULL = contextlib.nullcontext()


class _SpanCM:
    """Reusable-per-call context manager recording one span on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 args: Optional[Dict]):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        if self._tracer.annotate and self._tracer._annotation is not None:
            self._ann = self._tracer._annotation(self._name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record(self._name, self._t0, dur, self._args)
        return False


class SpanTracer:
    """Ring-buffer span recorder; see module docstring."""

    def __init__(self, capacity: int = 65536, enabled: bool = False,
                 annotate: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self.annotate = bool(annotate)
        self._lock = threading.Lock()
        self._ring: List[Optional[tuple]] = [None] * self.capacity
        self._head = 0          # next write index
        self._total = 0         # spans ever recorded (wraparound counter)
        self._t_origin = time.perf_counter_ns()
        self._annotation = None
        if annotate:
            self._load_annotation()

    def _load_annotation(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation

    def configure(self, enabled: Optional[bool] = None,
                  annotate: Optional[bool] = None) -> "SpanTracer":
        if enabled is not None:
            self.enabled = bool(enabled)
        if annotate is not None:
            self.annotate = bool(annotate)
            if self.annotate and self._annotation is None:
                self._load_annotation()
        return self

    # -- recording -----------------------------------------------------

    def span(self, name: str, args: Optional[Dict] = None):
        """Context manager timing the enclosed block. No-op when disabled."""
        if not self.enabled:
            return _NULL
        return _SpanCM(self, name, args)

    def instant(self, name: str, args: Optional[Dict] = None) -> None:
        """Zero-duration marker event."""
        if not self.enabled:
            return
        self._record(name, time.perf_counter_ns(), 0, args, ph="i")

    def _record(self, name, t0_ns, dur_ns, args, ph="X"):
        tid = threading.get_ident()
        with self._lock:
            self._ring[self._head] = (name, t0_ns, dur_ns, tid, args, ph)
            self._head = (self._head + 1) % self.capacity
            self._total += 1

    # -- export --------------------------------------------------------

    def spans(self) -> List[Dict]:
        """Recorded spans, oldest first (at most ``capacity``)."""
        with self._lock:
            n = min(self._total, self.capacity)
            start = (self._head - n) % self.capacity
            raw = [self._ring[(start + i) % self.capacity] for i in range(n)]
        return [{"name": s[0], "t0_ns": s[1], "dur_ns": s[2], "tid": s[3],
                 "args": s[4] or {}, "ph": s[5]} for s in raw
                if s is not None]

    @property
    def n_recorded(self) -> int:
        """Spans ever recorded (including those evicted by wraparound)."""
        with self._lock:
            return self._total

    @property
    def n_dropped(self) -> int:
        with self._lock:
            return max(0, self._total - self.capacity)

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._head = 0
            self._total = 0

    def chrome_trace(self) -> Dict:
        """Chrome-trace / Perfetto ``traceEvents`` JSON object."""
        pid = os.getpid()
        events = []
        for s in self.spans():
            ev = {
                "name": s["name"],
                "ph": s["ph"],
                "ts": (s["t0_ns"] - self._t_origin) / 1e3,   # µs
                "pid": pid,
                "tid": s["tid"],
                "args": s["args"],
            }
            if s["ph"] == "X":
                ev["dur"] = s["dur_ns"] / 1e3
            else:
                ev["s"] = "t"  # instant event scope: thread
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_spans": self.n_dropped}}

    def export(self, path: str) -> str:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


_default = SpanTracer()


def get_tracer() -> SpanTracer:
    """Process-wide default tracer. Disabled by default: library code calls
    ``get_tracer().span(...)`` freely; only an entry point (launcher,
    benchmark, test) flips it on via :func:`configure`."""
    return _default


def configure(enabled: Optional[bool] = None,
              annotate: Optional[bool] = None,
              capacity: Optional[int] = None) -> SpanTracer:
    """Configure the default tracer. Changing ``capacity`` clears it."""
    global _default
    if capacity is not None and capacity != _default.capacity:
        _default = SpanTracer(capacity=capacity, enabled=_default.enabled,
                              annotate=_default.annotate)
    return _default.configure(enabled=enabled, annotate=annotate)


@contextlib.contextmanager
def profile_window(logdir: str, tracer: Optional[SpanTracer] = None):
    """Capture a ``jax.profiler`` trace into ``logdir`` for the enclosed
    block, composing with the span tracer's annotations (spans appear as
    named ranges inside the device timeline). A profiler that cannot start
    raises: a traced window must never silently hold no trace."""
    import jax

    prev = None
    if tracer is not None:
        prev = (tracer.enabled, tracer.annotate)
        tracer.configure(enabled=True, annotate=True)
    try:
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    finally:
        if tracer is not None and prev is not None:
            tracer.configure(enabled=prev[0], annotate=prev[1])


_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLED = re.compile(r"\b(?:body|condition|to_apply)=%?([\w.\-]+)"
                     r"|\bbranch_computations=\{([^}]*)\}")


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` for every top-level instruction of an
    optimized HLO module (``compiled.as_text()``): the entry computation,
    while bodies and conditions, conditional branches, everything but the
    computations a fusion calls. The ``op_name`` is the named-scope path
    joined by ``/``, transforms wrapped round a scope's name
    (``transpose(jvp(layers))``); a fusion carries its root's.

    An instruction XLA inserted (a layout copy, a zero broadcast) has no
    metadata: it takes the ``op_name`` of its first user in the computation
    that has one, else that of the instruction calling its computation (a
    while loop for its body), else ``""``."""
    from repro.launch.hlo_analysis import parse_computations

    comps = parse_computations(hlo_text)
    fused, caller = set(), {}
    for comp in comps.values():
        for ins in comp.instrs:
            m = _CALLS.search(ins.rhs) if ins.op == "fusion" else None
            if m:
                fused.add(m.group(1))
                continue
            for m in _CALLED.finditer(ins.rhs):
                for callee in (m.group(1) or m.group(2)).split(","):
                    caller.setdefault(callee.strip(" %"), ins.name)
    out: Dict[str, str] = {}
    for comp in comps.values():
        if comp.name in fused:
            continue
        users: Dict[str, List[str]] = {}
        for ins in comp.instrs:
            for o in ins.operands:
                users.setdefault(o, []).append(ins.name)
        for ins in reversed(comp.instrs):   # users are printed after
            m = _OP_NAME.search(ins.rhs)
            out[ins.name] = m.group(1) if m else next(
                (out[u] for u in users.get(ins.name, ()) if out[u]), "")
    for _ in range(len(comps)):             # callers before callees
        changed = False
        for name, comp in comps.items():
            up = out.get(caller.get(name, ""), "")
            for ins in comp.instrs:
                if up and not out.get(ins.name, "x"):
                    out[ins.name] = up
                    changed = True
        if not changed:
            break
    return out
