"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 100 \
      --reduced --policy mirage --ckpt-dir /tmp/ckpt

Full published width by default (``--reduced`` for the CPU-scale config).
Layers are rematerialized and the LM-head cross-entropy runs in 512-token
chunks, so a qwen2-0.5b step at batch 4 x seq 512 fits one 16 GB chip.
On a cluster this process runs per host under
``jax.distributed.initialize()`` (flag --distributed). ``main`` returns the
run's summary (per-step losses and host wall-clock step times, compile
time, device memory of the compiled step).

Recommended XLA flags for real TPU runs (latency-hiding overlap of the FSDP
all-gathers and gradient reduce-scatters with compute):
  --xla_tpu_enable_latency_hiding_scheduler=true
  --xla_tpu_overlap_compute_collective_tc=true
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.core.precision import get_policy
from repro.launch import compile_cache
from repro.data.pipeline import SyntheticLM, SyntheticLMConfig, with_extras
from repro.models import build_model
from repro.models.lm import LMCallOptions
from repro.runtime.elastic import (PreemptionGuard, StragglerMitigator,
                                   fault_tolerant_train_loop)
from repro.runtime.trainer import init_train_state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--policy", default="mirage",
                    help="fp32|bf16|int8|mirage|mirage_faithful|mirage_rns|"
                         "mirage_rns_noisy|mirage_rrns")
    ap.add_argument("--snr-db", type=float, default=None,
                    help="detector SNR for the analog-channel policies")
    ap.add_argument("--noise-seed", type=int, default=None,
                    help="static per-GEMM-site error pattern seed for "
                         "keyless noisy training")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", default="none", choices=["none", "bfp"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--trace-export", default=None, metavar="FILE",
                    help="enable the span tracer (train.step / "
                         "train.data_next / train.host_sync) and write a "
                         "Chrome-trace JSON here at exit")
    args = ap.parse_args(argv)

    compile_cache.enable()
    if args.distributed:
        jax.distributed.initialize()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    overrides = {}
    if args.snr_db is not None:
        overrides["snr_db"] = args.snr_db
    if args.noise_seed is not None:
        overrides["noise_seed"] = args.noise_seed
    policy = get_policy(args.policy, **overrides)
    tc = TrainConfig(policy=policy, optimizer=args.optimizer, lr=args.lr,
                     microbatches=args.microbatches,
                     grad_compression=args.grad_compression, seed=args.seed)
    model = build_model(cfg, policy, LMCallOptions(
        q_chunk=64, kv_chunk=64, remat=True, ce_chunk=512))

    data = with_extras(
        SyntheticLM(SyntheticLMConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq,
            batch_size=args.batch, seed=args.seed,
            shard_id=jax.process_index(), num_shards=jax.process_count())),
        cfg)

    state = init_train_state(model, tc, jax.random.PRNGKey(args.seed))
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state, meta = ckpt.restore(state)
        print(f"resumed from step {int(state['step'])}")

    if args.trace_export:
        from repro.obs import trace as obs_trace
        obs_trace.configure(enabled=True)

    report = {}
    t0 = time.time()
    if ckpt:
        state, metrics = fault_tolerant_train_loop(
            model, tc, state, iter(data), args.steps, ckpt,
            ckpt_every=args.ckpt_every, guard=PreemptionGuard(),
            straggler=StragglerMitigator())
    else:
        from repro.runtime.trainer import train_loop
        state, metrics = train_loop(model, tc, state, iter(data), args.steps,
                                    report=report)
    dt = time.time() - t0
    print(f"trained {args.steps} steps in {dt:.1f}s host wall-clock "
          f"({args.steps / dt:.2f} steps/s); final loss "
          f"{float(metrics['loss']):.4f}")
    if args.trace_export:
        from repro.obs import trace as obs_trace
        tracer = obs_trace.get_tracer()
        tracer.export(args.trace_export)
        print(f"chrome trace ({tracer.n_recorded} spans) -> "
              f"{args.trace_export}")
    return report


if __name__ == "__main__":
    main()
