"""Training runtime: train state, step function, microbatch accumulation,
fault-tolerance hooks. Pure functions — distribution comes entirely from the
sharding specs the launcher attaches via jit in/out_shardings.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import grad_compress
from repro.optim.optimizers import clip_by_global_norm, make_optimizer
from repro.optim.schedules import constant


def init_train_state(model, train_cfg: TrainConfig, key) -> Dict[str, Any]:
    params = model.init(key)
    opt_init, _ = make_optimizer(train_cfg)
    state = {"params": params, "opt": opt_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if train_cfg.grad_compression == "bfp":
        state["err"] = grad_compress.init_error_buffer(params)
    return state


def abstract_train_state(model, train_cfg: TrainConfig):
    """ShapeDtypeStruct tree of the train state — no allocation (dry-run)."""
    return jax.eval_shape(
        lambda k: init_train_state(model, train_cfg, k), jax.random.PRNGKey(0))


_QUANT_LEAF = ("w", "emb", "gate", "up", "down")


def _prequantize_params(params, policy, dtype):
    """Weight-stationary quantization: put every GEMM weight on the BFP grid
    ONCE (grouped along its contraction dim = axis -2), exactly as the
    photonic core programs a tile once and streams inputs against it.
    BFP(b_m<=6) grid values are bf16-exact, so bf16 storage is lossless."""
    from repro.core import bfp

    def q(path, p):
        keys = [k.key if hasattr(k, "key") else str(k) for k in path]
        leaf = keys[-1]
        if p.ndim < 2 or leaf not in _QUANT_LEAF:
            return p
        if leaf == "emb":
            return p  # embedding gathers stay FP32 (digital side in paper)
        moved = jnp.moveaxis(p, -2, -1)
        qv = bfp.bfp_fake_quant(moved, policy.b_m, policy.g, policy.rounding)
        return jnp.moveaxis(qv, -1, -2).astype(dtype)

    return jax.tree_util.tree_map_with_path(q, params)


def make_train_step(model, train_cfg: TrainConfig,
                    lr_schedule: Optional[Callable] = None):
    """Returns train_step(state, batch) -> (state, metrics).

    Microbatching: batch is split along axis 0 into `microbatches` slices and
    gradients are accumulated with lax.scan (constant memory in the number of
    microbatches; remat inside the model bounds activation memory).
    """
    from repro.core import backends

    _, opt_update = make_optimizer(train_cfg)
    lr_schedule = lr_schedule or constant(train_cfg.lr)
    nmb = train_cfg.microbatches
    # weight-stationary quantization applies when the GEMM backend declares
    # it honours pre-quantized weight operands (capability flag, not a
    # mode-name comparison — new registered backends opt in themselves)
    wsq = (train_cfg.weight_stationary_quant
           and backends.resolve(train_cfg.policy).supports_weight_stationary)
    qdtype = (jnp.bfloat16 if train_cfg.quant_param_dtype == "bfloat16"
              else jnp.float32)

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def train_step(state, batch):
        params = state["params"]
        if wsq:
            # quantize once per step; grads flow straight-through to the FP32
            # master below (paper Eq. 4 semantics).
            with jax.named_scope("prequantize"):
                params = _prequantize_params(params, train_cfg.policy, qdtype)

        if nmb > 1:
            def split(x):
                return x.reshape((nmb, x.shape[0] // nmb) + x.shape[1:])
            mbatch = jax.tree_util.tree_map(split, batch)

            def acc_body(acc, mb):
                (l, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(jnp.add, acc_g, g)
                return (acc_g, acc_l + l), metrics

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), ms = jax.lax.scan(
                acc_body, (zero_g, jnp.zeros(())), mbatch)
            grads = jax.tree_util.tree_map(lambda g: g / nmb, grads)
            loss = loss_sum / nmb
            metrics = jax.tree_util.tree_map(lambda m: m[-1], ms)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)

        if train_cfg.grad_compression == "bfp":
            grads, new_err = grad_compress.compress_with_error_feedback(
                grads, state["err"], train_cfg.policy.b_m, train_cfg.policy.g)

        if train_cfg.grad_clip > 0:
            with jax.named_scope("clip"):
                grads, gnorm = clip_by_global_norm(grads, train_cfg.grad_clip)
        else:
            gnorm = jnp.zeros(())

        with jax.named_scope("optimizer"):
            lr = lr_schedule(state["step"])
            # the optimizer always updates the FP32 MASTER weights (Eq. 4)
            new_params, new_opt = opt_update(grads, state["opt"],
                                             state["params"], lr)
            new_state = dict(state, params=new_params, opt=new_opt,
                             step=state["step"] + 1)
        if train_cfg.grad_compression == "bfp":
            new_state["err"] = new_err
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return train_step


@dataclasses.dataclass
class StepTimer:
    """Straggler monitor: per-step EMA + slow-step flags (runtime/elastic.py
    consumes these to trigger mitigation at scale)."""
    ema: float = 0.0
    beta: float = 0.9
    slow_factor: float = 2.0
    slow_steps: int = 0

    def record(self, dt: float) -> bool:
        slow = self.ema > 0 and dt > self.slow_factor * self.ema
        self.ema = dt if self.ema == 0 else (self.beta * self.ema
                                             + (1 - self.beta) * dt)
        if slow:
            self.slow_steps += 1
        return slow


def memory_summary(compiled) -> Dict[str, int]:
    """Device bytes of a compiled program (``memory_analysis()``): its
    arguments, outputs, the outputs aliased onto donated arguments, and
    temporaries. ``peak`` = arguments + outputs - aliased + temporaries."""
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, f"{k}_size_in_bytes"))
           for k in ("argument", "output", "alias", "temp")}
    out["peak"] = (out["argument"] + out["output"] - out["alias"]
                   + out["temp"])
    return out


def train_loop(model, train_cfg: TrainConfig, state, data_iter, n_steps: int,
               checkpointer=None, ckpt_every: int = 0, log_every: int = 10,
               log_fn=print, registry=None, report: Optional[dict] = None):
    """Single-host training loop with checkpoint/restart + straggler hooks.

    The step donates the train state (params + optimizer moments are
    updated in place; the caller's ``state`` is consumed) and is compiled
    ahead of the first step, whose device memory is logged before it runs.

    Observability: each phase of the loop opens a tracer span
    (``train.data_next`` / ``train.step`` / ``train.host_sync`` — free when
    the tracer is disabled) and step latency/count land in ``registry``
    (default: the process registry) as ``train_step_seconds`` /
    ``train_steps_total``. ``report``, when given, receives
    ``compile_seconds``, ``memory`` (:func:`memory_summary`) and per-step
    ``losses`` and ``step_seconds`` (host wall-clock to the loss)."""
    reg = registry if registry is not None else obs_metrics.get_registry()
    h_step = reg.histogram("train_step_seconds",
                           "walltime per optimizer step (dispatch + sync)")
    c_steps = reg.counter("train_steps_total", "optimizer steps completed")
    g_slow = reg.gauge("train_slow_steps", "straggler-flagged steps so far")
    tr = obs_trace.get_tracer()
    step_fn = jax.jit(make_train_step(model, train_cfg), donate_argnums=0)
    report = report if report is not None else {}
    report.update(losses=[], step_seconds=[])
    timer = StepTimer()
    metrics = {}
    for i in range(n_steps):
        with tr.span("train.data_next"):
            batch = next(data_iter)
        if i == 0:
            t0 = time.perf_counter()
            step_fn = step_fn.lower(state, batch).compile()
            report["compile_seconds"] = time.perf_counter() - t0
            report["memory"] = memory_summary(step_fn)
            if log_every:
                gib = {k: f"{v / 2**30:.2f}" for k, v in
                       report["memory"].items()}
                log_fn(f"train step compiled in "
                       f"{report['compile_seconds']:.1f}s (host wall-clock);"
                       f" device memory GiB: {gib}")
        t0 = time.perf_counter()
        with tr.span("train.step", {"i": i}):
            state, metrics = step_fn(state, batch)
        with tr.span("train.host_sync"):
            loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        report["losses"].append(loss)
        report["step_seconds"].append(dt)
        slow = timer.record(dt)
        h_step.observe(dt)
        c_steps.inc()
        g_slow.set(timer.slow_steps)
        step = int(state["step"])
        if log_every and (i % log_every == 0 or i == n_steps - 1):
            log_fn(f"step {step}: loss={loss:.4f} "
                   f"ppl={float(metrics.get('ppl', 0)):.2f} "
                   f"gnorm={float(metrics['grad_norm']):.3f}"
                   + (" [SLOW STEP]" if slow else ""))
        if checkpointer is not None and ckpt_every and step % ckpt_every == 0:
            checkpointer.save(state, step)
    return state, metrics
