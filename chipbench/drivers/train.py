"""Driver for traffic of kind ``train``: the trainer's step on a token stream.

Set-up builds one object, the trainer's compiled step (``make_train_step``,
jitted with the state donated) with its state from a jitted init on the
device, checks that the program's parameter tree is the one the cell's
reference reads from the configuration (leaf by leaf, name and shape),
and drives it through the mix's first ``check_steps`` steps on
distinct batches, each ending in the loss fetch as ``train_loop`` does.
Those steps give the readings that are compared with the reference: each
step's loss, each leaf's norm of the first gradient (recovered from
AdamW's first moment after one step) and of the parameters' change over
the steps. The same object then runs the measured window: steps on fresh
batches until ``--seconds`` have passed, every step ending in the loss
fetch. ``train_tokens_per_s`` is all tokens of the window over its
wall time.

With ``--trace 1`` the window is a profiler trace of ``trace_seconds``
instead, with the spans ``train.data_next``, ``train.step`` and
``train.host_sync`` around the loop's phases.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen, program, reduce
from chipbench.harness import BenchError


def run(r) -> None:
    from repro.configs.base import TrainConfig
    from repro.runtime.trainer import init_train_state, make_train_step

    t_enter = time.perf_counter()
    conf, mix, reference = r.cell.config, r.cell.traffic, r.cell.reference
    policy = program.policy(r.program_policy or conf["policy"])
    model = program.model(conf, policy, mix)
    tc = TrainConfig(policy=policy, optimizer=mix["optimizer"],
                     lr=mix["lr"], grad_clip=mix["grad_clip"])
    b1 = tc.beta1
    key = jnp.asarray(gen.seed_words(r.seed))
    data = gen.SyntheticLM(conf["vocab_size"], mix["seq"], mix["batch"],
                           r.seed)
    tokens_per_step = mix["batch"] * mix["seq"]

    init = jax.jit(lambda k: init_train_state(model, tc, k))
    state = jax.block_until_ready(init(key))
    t_init = time.perf_counter()
    check_tree(state["params"], reference.param_shapes(conf))
    batch = next(data)
    step = jax.jit(make_train_step(model, tc), donate_argnums=0).lower(
        state, batch).compile()
    t_compile = time.perf_counter()
    r.layer["step_memory"] = step_memory(step)
    change_norms = jax.jit(lambda p, k: reference.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, p, init(k)["params"])))

    losses = []
    for i in range(mix["check_steps"]):
        if i:
            batch = next(data)
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        if i == 0:
            # AdamW's first moment after one step is (1 - b1) x the clipped
            # gradient; fetched as it is, so the device holds no extra copy
            g_host = {k: np.asarray(v) / np.float32(1 - b1) for k, v in
                      _by_name(jax.device_get(state["opt"]["m"])).items()}
    g1 = {k: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
          for k, v in g_host.items()}
    change = jax.device_get(change_norms(state["params"], key))
    r.layer["flops_per_token"] = reference.train_flops_per_token(conf,
                                                                 mix["seq"])
    r.layer["setup_phases"] = phases = {
        "runtime_s": t_enter - r.t_start, "weights_s": t_init - t_enter,
        "step_program_s": t_compile - t_init,
        "check_steps_s": time.perf_counter() - t_compile}
    print("set-up: " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
          + "; step program (bytes): " + ", ".join(
              f"{k} {v}" for k, v in r.layer["step_memory"].items()))

    if r.trace:
        state = _traced_window(r, state, step, data, mix, tokens_per_step)
    else:
        r.count_compiles(True)
        t0 = time.perf_counter()
        r.e2e["setup_s"] = t0 - r.t_start
        n = 0
        while time.perf_counter() - t0 < r.seconds:
            state, metrics = step(state, next(data))
            float(metrics["loss"])
            n += 1
        dt = time.perf_counter() - t0
        compiles = r.count_compiles(False)
        r.e2e["train_tokens_per_s"] = n * tokens_per_step / dt
        print(f"window: {n} steps in {dt:.4f} s; compilations inside the "
              f"window: {compiles}")
    r.attempted = mix["check_steps"]
    r.read_memory_peak()
    r.memory_peak_bytes = max(r.memory_peak_bytes,
                              r.layer["step_memory"]["peak"])
    del state, step
    gc.collect()

    ref = reference.train_readings(
        conf, conf["policy"], key,
        [data.batch_at(i) for i in range(mix["check_steps"])],
        lr=mix["lr"], clip=mix["grad_clip"], b1=b1, ce_chunk=mix["ce_chunk"],
        grad=g_host)
    del g_host
    readings = compare(losses, g1, change, ref)
    r.failed = 0 if all(np.isfinite(losses)) else r.attempted
    for name, value in readings.items():
        if name in r.cell.limits:
            r.check(name, value)
    r.layer["calibration"] = {
        "readings": readings, "grad_diff": ref["grad_diff"],
        "losses": losses, "ref_losses": ref["losses"],
        "grad": g1, "ref_grad": ref["grad"],
        "change": {k: float(v) for k, v in change.items()},
        "ref_change": ref["change"]}


def compare(losses, grad, change, ref):
    """The numbers that may decide ``correct``.

    * ``loss_gap``: the largest relative gap of a step's loss;
    * ``grad_gap``: over leaves, the largest gap between the program's and
      the reference's norm of the first gradient, over the reference's
      norm of that leaf or of the median leaf, whichever is larger;
    * ``update_gap``: the same for the norm of the parameters' change over
      the check steps, leaving out leaves whose reference gradient is under
      a thousandth of the median leaf's (zero but for rounding, as a key
      bias under softmax: Adam moves them by round-off alone);
    * ``grad_diff``: over the same leaves, the median of the norm of the
      difference between the program's first gradient and the reference's,
      over the reference's norm of that leaf;
    * ``final_norm_grad_diff``: the same for the final norm's scale, whose
      gradient sums over every token and the whole vocabulary.

    Only the numbers the cell's limits file names are compared.
    """
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       ref["losses"]))
    rg = ref["grad"]
    med_g = float(np.median(list(rg.values())))
    grad_gap = max(abs(float(grad[k]) - rg[k]) / max(rg[k], med_g)
                   for k in rg)
    moved = [k for k in rg if rg[k] >= 1e-3 * med_g]
    rc = ref["change"]
    med_c = float(np.median([rc[k] for k in moved]))
    update_gap = max(abs(float(change[k]) - rc[k]) / max(rc[k], med_c)
                     for k in moved)
    rd = ref["grad_diff"]
    grad_diff = float(np.median([rd[k] / rg[k] for k in moved]))
    head = "final_norm/scale"
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap, "grad_diff": grad_diff,
            "final_norm_grad_diff": rd[head] / rg[head]}


def check_tree(params, want) -> None:
    """Refuse a program whose parameter tree differs from the reference's
    ``{leaf name: shape}``, naming the first leaf that differs: a size the
    program did not take from the configuration would be measured, and
    compared, as the wrong model."""
    got = {k: tuple(x.shape) for k, x in _by_name(params).items()}
    want = {k: tuple(v) for k, v in want.items()}
    for k in sorted(set(got) | set(want)):
        if got.get(k) != want.get(k):
            raise BenchError(
                f"parameter {k}: the program has "
                f"{got.get(k, 'no such leaf')}, the configuration states "
                f"{want.get(k, 'no such leaf')}")


def step_memory(compiled) -> dict:
    """The compiled step's device memory as XLA plans it: its state in and
    out (aliased, as it is donated) and its temporaries. The runtime's
    ``peak_bytes_in_use`` counts the arrays the process holds (weights and
    AdamW state), not the step's temporaries, so the run's
    ``memory_peak_bytes`` is the larger of the two."""
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")
    out = {k.replace("_size_in_bytes", ""): int(getattr(ma, k, 0) or 0)
           for k in keys}
    out["peak"] = (out["argument"] + out["output"] - out["alias"]
                   + out["temp"])
    return out


def _by_name(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(q, "key", q)) for q in path): x
            for path, x in flat}


def _traced_window(r, state, step, data, mix, tokens_per_step):
    tdir = reduce.trace_dir(r)
    n = 0
    jax.profiler.start_trace(str(tdir))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < mix["trace_seconds"]:
            with jax.profiler.TraceAnnotation("train.data_next"):
                batch = next(data)
            with jax.profiler.TraceAnnotation("train.step"):
                state, metrics = step(state, batch)
            with jax.profiler.TraceAnnotation("train.host_sync"):
                float(metrics["loss"])
            n += 1
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()
    r.layer["steps"] = n
    r.layer["tokens_per_s"] = n * tokens_per_step / dt
    r.layer["trace"] = reduce.reduce_dir(tdir, r.keep_trace)
    r.breakdown = reduce.breakdown(r.layer["trace"])
    return state
