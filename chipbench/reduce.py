"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData``. A TPU device is a plane named
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per execution of
a jitted program, named ``jit_<fn>(<hash>)``) and a line ``XLA Ops`` (one
event per HLO operation, named by its HLO text). Host spans
(``jax.profiler.TraceAnnotation``) are events on the threads of the plane
``/host:CPU``; the benchmark brackets the traced window with the span
``bench.window``.

The device's clock is not the host's. The offset is taken from the host
event ``CompleteCallbacks``, which the runtime records once it sees a
program finish (same ``run_id``): the host cannot see the end before it
happens, so the offset is the least (host notice - device end) over all
programs. Within the window, reduced to the device's clock:

* ``busy_s``: the union of the intervals in which an operation ran,
  averaged over the devices; ``window_s`` the window's length;
* ``ops``: self time (nested operations subtracted) by operation, with its
  class ``matmul`` (a convolution or dot, or an output fusion rooted at
  one) or ``other``;
* ``programs``: device time and executions by jitted program;
* ``gaps``: every idle interval, with the innermost host span covering
  its midpoint (``(none)`` where no benchmark span covers it).
"""

from __future__ import annotations

import bisect
import re
import shutil
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW = "bench.window"
SPAN_PREFIXES = ("train.", "serve.", "bench.")
_MODULE_HASH = re.compile(r"\(\d+\)$")


def trace_dir(run) -> Path:
    from chipbench.harness import WORK_DIR

    d = WORK_DIR / "trace"
    shutil.rmtree(d, ignore_errors=True)
    return d


def find_xplane(d: Path) -> Path:
    found = sorted(Path(d).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return found[-1]


def reduce_dir(d: Path, keep_in: Path = None) -> Dict:
    """Reduce the trace under ``d`` and delete it (after copying the
    ``.xplane.pb`` into ``keep_in``, where given)."""
    path = find_xplane(d)
    out = reduce_file(path)
    if keep_in is not None:
        Path(keep_in).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(keep_in) / path.name)
    shutil.rmtree(d, ignore_errors=True)
    return out


def op_class(name: str) -> str:
    """``matmul`` for a convolution or dot, or an output fusion (XLA's
    fusion kind for a convolution with its fused epilogue); else
    ``other``."""
    head = name.split("(", 1)[0]
    if " convolution" in head or " dot" in head:
        return "matmul"
    if "kind=kOutput" in name or "convolution(" in name or " dot(" in name:
        return "matmul"
    return "other"


def op_short(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(events: List[Tuple[float, float, str]]):
    """Self time of each (start, end, name), nested events subtracted."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    self_t = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i, (a, b, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][1]:
            self_t[stack[-1]] -= b - a
        stack.append(i)
    return events, self_t


def reduce_file(path: Path) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans: List[Tuple[float, float, str]] = []
    notices: Dict[int, float] = {}
    devices = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
                    elif e.name == "CompleteCallbacks":
                        st = {k: v for k, v in e.stats}
                        if "run_id" in st:
                            rid = int(st["run_id"])
                            notices[rid] = min(notices.get(rid, 1e30),
                                               e.start_ns)
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            devices.append((plane.name, lines))
    win = [s for s in spans if s[2] == WINDOW]
    if not win or not devices:
        raise ValueError(f"trace {path} has no {WINDOW!r} span or no TPU "
                         f"device plane")
    w0, w1 = win[0][0], win[0][1]

    offsets = []
    for _, lines in devices:
        for e in lines.get("XLA Modules", []):
            rid = {k: v for k, v in e.stats}.get("run_id")
            if rid is not None and int(rid) in notices:
                offsets.append(notices[int(rid)] - (e.start_ns
                                                    + e.duration_ns))
    shift = min(offsets) if offsets else 0.0
    d0, d1 = w0 - shift, w1 - shift

    busy_total = 0.0
    ops: Dict[str, Dict] = {}
    programs: Dict[str, Dict] = {}
    gaps: List[Tuple[float, float]] = []
    for _, lines in devices:
        evs = [(max(e.start_ns, d0), min(e.start_ns + e.duration_ns, d1),
                e.name) for e in lines.get("XLA Ops", [])
               if e.start_ns < d1 and e.start_ns + e.duration_ns > d0]
        busy = _union([(a, b) for a, b, _ in evs if b > a])
        busy_total += sum(b - a for a, b in busy)
        edges = [d0] + [x for iv in busy for x in iv] + [d1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _MODULE_HASH.sub("", e.name))
                      for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        evs, self_t = _self_times(evs)
        for (a, b, name), st in zip(evs, self_t):
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= a else "?"
            k = f"{prog}/{op_short(name)}"
            o = ops.setdefault(k, {"s": 0.0, "n": 0, "class": op_class(name)})
            o["s"] += st * 1e-9
            o["n"] += 1
        for e in lines.get("XLA Modules", []):
            a, b = max(e.start_ns, d0), min(e.start_ns + e.duration_ns, d1)
            if b <= a:
                continue
            k = _MODULE_HASH.sub("", e.name)
            p = programs.setdefault(k, {"s": 0.0, "n": 0})
            p["s"] += (b - a) * 1e-9
            p["n"] += 1

    inner = sorted((s for s in spans if s[2] != WINDOW),
                   key=lambda s: s[1] - s[0])
    gap_list = []
    for a, b in gaps:
        mid = (a + b) / 2 + shift       # back on the host's clock
        cover = next((s[2] for s in inner if s[0] <= mid <= s[1]), "(none)")
        gap_list.append({"span": cover, "s": (b - a) * 1e-9})
    n_dev = len(devices)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_total * 1e-9 / n_dev,
            "devices": n_dev, "clock_shift_ns": shift, "ops": ops,
            "programs": programs, "gaps": gap_list}


def matmul_seconds(red: Dict) -> Tuple[float, float]:
    """(device seconds in matmul ops, in other ops) over the window."""
    mm = sum(o["s"] for o in red["ops"].values() if o["class"] == "matmul")
    other = sum(o["s"] for o in red["ops"].values() if o["class"] != "matmul")
    return mm, other


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The ``breakdown`` of a traced result line: the device operations
    that took most time, and the longest idle gaps with the host span that
    covered each."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1]["s"])[:top]
    gaps = sorted(red["gaps"], key=lambda g: -g["s"])[:top]
    return {"device_ops": [[k, v["s"]] for k, v in ops],
            "idle_gaps": [[g["span"], g["s"]] for g in gaps]}
