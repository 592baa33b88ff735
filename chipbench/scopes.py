"""Device time of the train step by program layer, from named scopes.

The program names its device work with ``jax.named_scope``; the scope path
reaches each HLO instruction's ``op_name`` metadata, and a fusion carries
the one of the instruction XLA built it from. :func:`scope_map` compiles
the cell's step again after the run, as the driver built it, and reads
``{instruction: op_name}`` from its optimized HLO with
``repro.obs.trace.op_scopes``. Each operation of the trace (``reduce``'s
``ops``, keyed ``<program>/<instruction>``) then falls in exactly one
bucket, by the first rule that matches the scope names on its path:

1. ``optimizer``: ``optimizer`` or ``clip``;
2. ``gemm_quantize``: ``quantize_x``, ``quantize_w`` or ``prequantize``
   (forward, remat and both backward GEMMs);
3. ``attention_core``: ``attn_core``, its matmuls included;
4. ``layer_scan``: ``layers`` and no sub-layer or GEMM scope: the layer
   scan's stacking copies and slices;
5. ``other_scoped``: any other scope named below;
6. ``unscoped``: no scope named below, or an instruction the map lacks
   (``missing`` counts the latter apart).

A fusion takes one scope, so rounding that XLA fuses into a matmul's
fusion counts as ``matmul``, not as ``gemm_quantize``.

The names below are the program's: ``repro.obs.trace.SCOPE_NAMES`` where
the program declares them, else the ones ``repro.obs.trace``'s docstring
lists. :func:`ms_per_step_in` reads any scope by name, one a bucket rule
names or not, so a metric of a scope a new model opens is a new file.
"""

from __future__ import annotations

import contextlib
import re
import time
from typing import Dict, Iterable, List, Optional, Tuple

from repro.obs import trace as _trace

QUANTIZE = frozenset({"quantize_x", "quantize_w", "prequantize"})
# every scope the program opens, as ``repro.obs.trace``'s docstring lists
# them, for a program that does not declare its own
_LISTED = QUANTIZE | frozenset({
    "gemm", "gemm_dx", "gemm_dw", "matmul", "embed", "layers", "attn_norm",
    "attn_qkv", "attn_core", "attn_out", "mlp_norm", "mlp", "final_norm",
    "head_ce", "clip", "optimizer"})
SCOPES = frozenset(getattr(_trace, "SCOPE_NAMES", _LISTED))
BUCKETS = ("optimizer", "gemm_quantize", "attention_core", "layer_scan",
           "other_scoped", "unscoped")
# a transform wraps the scope it applies to: transpose(jvp(layers))
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
# the persistent compilation cache keys a program with its metadata
# stripped unless this is set, and would hand back an executable compiled
# under other scope names
_KEY_ON_METADATA = "jax_compilation_cache_include_metadata_in_key"


def segments(op_name: str) -> set:
    """The segments of an ``op_name`` path (``/``-separated; XLA joins
    merged instructions' names with ``;``), transforms unwrapped. A jitted
    function's segment (``jit(clip)``) is a function, not a scope."""
    found = set()
    for seg in re.split(r"[/;]", op_name):
        m = _TRANSFORM.match(seg)
        while m:
            seg = m.group(1)
            m = _TRANSFORM.match(seg)
        found.add(seg)
    return found


def scopes_on(op_name: str) -> set:
    """The scope names of :data:`SCOPES` on an ``op_name`` path."""
    return segments(op_name) & SCOPES


def bucket(op_name: str) -> str:
    """The bucket of an instruction by its ``op_name`` path."""
    words = scopes_on(op_name)
    if words & {"optimizer", "clip"}:
        return "optimizer"
    if words & QUANTIZE:
        return "gemm_quantize"
    if "attn_core" in words:
        return "attention_core"
    if words == {"layers"}:     # no sub-layer or GEMM scope under it
        return "layer_scan"
    return "other_scoped" if words else "unscoped"


@contextlib.contextmanager
def named_compile(on: bool):
    """Compile inside the block with the persistent cache keyed on the
    program's metadata, where ``on``: named scopes change only metadata, so
    without it a cached executable of the same program under other (or no)
    scope names would come back, and :func:`scope_map` would read its
    names."""
    import jax

    if not on:
        yield
        return
    was = getattr(jax.config, _KEY_ON_METADATA)
    jax.config.update(_KEY_ON_METADATA, True)
    try:
        yield
    finally:
        jax.config.update(_KEY_ON_METADATA, was)


def _step(run, named: bool):
    """The optimized HLO of the cell's train step, compiled as the driver
    compiles it, from the shapes of its state and batch."""
    import jax
    import jax.numpy as jnp

    from chipbench import program
    from repro.configs.base import TrainConfig
    from repro.runtime.trainer import init_train_state, make_train_step

    conf, mix = run.cell.config, run.cell.traffic
    policy = program.policy(run.program_policy or conf["policy"])
    model = program.model(conf, policy, mix)
    tc = TrainConfig(policy=policy, optimizer=mix["optimizer"],
                     lr=mix["lr"], grad_clip=mix["grad_clip"])
    state = jax.eval_shape(lambda k: init_train_state(model, tc, k),
                           jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    with named_compile(named):
        return jax.jit(make_train_step(model, tc), donate_argnums=0).lower(
            state, batch).compile().as_text()


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$", re.M)
_META = re.compile(r", metadata=\{[^}]*\}")
_REF = re.compile(r"%[\w.\-]+")


def align(ran: str, fresh: str, scopes: Dict[str, str]) -> Dict[str, str]:
    """``scopes`` (keyed by ``fresh``'s instruction names) keyed by the
    names ``ran`` gives the same instructions. Both are one program's
    optimized HLO, alike but for metadata, which can renumber instructions
    (reshapes); matched by position where every instruction agrees with
    names and metadata left out, else by name."""
    a, b = _INSTR.findall(ran), _INSTR.findall(fresh)

    def bare(rhs):
        return _REF.sub("%", _META.sub("", rhs))
    if len(a) != len(b) or any(bare(x) != bare(y)
                               for (_, x), (_, y) in zip(a, b)):
        return scopes
    return {x: scopes[y] for (x, _), (y, _) in zip(a, b) if y in scopes}


def scope_map(run) -> Optional[Dict]:
    """``{"program", "scopes", "seconds"}`` for the run's step: its
    module's name, ``{instruction: op_name}`` under the names of the
    executable the window ran, and the host seconds it took; kept in
    ``run.layer["op_scopes"]``. ``None`` for a program without
    ``op_scopes``.

    The executable that ran may be a persistent-cache entry compiled with
    other metadata (the cache keys a program with it stripped), so its own
    ``op_name``s can be stale: a compile as the driver's (the same entry)
    gives the names that ran, a compile keyed on metadata
    (:func:`named_compile`) gives the scopes."""
    if "op_scopes" in run.layer:
        return run.layer["op_scopes"]
    try:
        from repro.obs.trace import op_scopes
    except ImportError:
        run.layer["op_scopes"] = None
        return None
    t0 = time.perf_counter()
    ran, fresh = _step(run, named=False), _step(run, named=True)
    run.layer["op_scopes"] = {
        "program": re.match(r"HloModule ([\w.\-]+)", ran).group(1),
        "scopes": align(ran, fresh, op_scopes(fresh)),
        "seconds": time.perf_counter() - t0}
    return run.layer["op_scopes"]


def _ops(run) -> Optional[Tuple[List[Tuple[Optional[str], float]], int]]:
    """``([(op_name, seconds), ...], steps)`` of the traced window, the
    ``op_name`` ``None`` for an instruction the map lacks; ``None`` where
    the trace, the step count or the map is absent."""
    t, steps = run.layer.get("trace"), run.layer.get("steps")
    if not t or not steps:
        return None
    m = scope_map(run)
    if not m:
        return None
    ops = []
    for key, op in t["ops"].items():
        prog, _, ins = key.rpartition("/")
        ops.append((m["scopes"].get(ins) if prog == m["program"] else None,
                    op["s"]))
    return ops, steps


def split(run) -> Optional[Dict[str, float]]:
    """Seconds of device self time per step in each of :data:`BUCKETS`, and
    ``missing``; ``None`` where the trace, the step count or the map is
    absent, or where no operation carries a scope named here."""
    got = _ops(run)
    if got is None:
        return None
    ops, steps = got
    out = dict.fromkeys(BUCKETS + ("missing",), 0.0)
    for name, s in ops:
        if name is None:
            out["unscoped"] += s
            out["missing"] += s
        else:
            out[bucket(name)] += s
    if not any(out[b] for b in BUCKETS[:-1]):
        return None
    return {k: v / steps for k, v in out.items()}


def ms_per_step(run, name: str) -> Optional[float]:
    """One bucket of :func:`split` in milliseconds per step."""
    s = split(run)
    return None if s is None else 1e3 * s[name]


def ms_per_step_in(run, names: Iterable[str]) -> Optional[float]:
    """Device self time per step, in milliseconds, of the operations whose
    scope path holds any of ``names``; ``None`` where the trace, the step
    count or the map is absent, or where no operation is under them."""
    got = _ops(run)
    if got is None:
        return None
    ops, steps = got
    names = set(names)
    hit = [s for name, s in ops if name and segments(name) & names]
    return 1e3 * sum(hit) / steps if hit else None
