"""Serving launcher: the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --requests 8

Serves the model at its published widths by default; ``--reduced`` switches
to the CPU-scale config (as in ``launch/train.py``).

Knobs: ``--engine batched`` (one jitted decode over the stacked slot cache;
default) vs ``--engine oracle`` (the retained per-slot parity loop);
``--cache-layout paged`` switches the per-slot KV rings to the block-table
page pool (``--block-size``/``--n-blocks`` size it — memory scales with
live tokens instead of slots x cap); ``--prefill-chunk N`` streams prompts
through the decode loop N tokens per tick (piggybacked prefill, paged
only) so long arrivals don't stall active streams;
``--policy mirage_rns_noisy --snr-db 30 --noise-seed 7`` serves under the
analog channel with fresh noise per tick; ``--sample`` switches greedy
argmax to device-side categorical sampling; ``--prefix-cache`` shares
matched whole-prompt-prefix blocks copy-on-write across slots (paged
only; ``--shared-prefix N`` makes the synthetic prompts actually share
their first N tokens so hits occur); ``--spec-k K`` self-drafts K tokens
per tick and verifies them in one jitted step (paged + greedy only,
token-identical to plain greedy decode).

Robustness: ``--chaos SPEC`` injects the seeded fault schedule (SNR
collapses, burst storms, stuck detector channels, block-pool squeezes,
prefill-worker crashes, host-transfer corruption — see
``repro.runtime.faults``); ``--guardian`` drains through the SNR guardian's
verify-before-commit windows (``repro.runtime.resilience``), escalating
RRNS redundancy and hard-falling-back to fp32 when the analog-health
counters report uncorrectable faults; ``--ttl-s`` / ``--queue-ttl-s`` give
requests decode/admission deadlines (terminal status ``timed_out``);
``--max-queue-depth`` caps admission (rejected with a retry-after hint);
``--max-retries`` bounds retries of fault-aborted requests.

Observability: ``--metrics-port P`` serves the engine's metrics registry
over HTTP (``/metrics`` Prometheus text, ``/metrics.json`` snapshot,
``/trace`` Chrome trace; port 0 picks a free one); ``--trace-export F``
enables the span tracer and writes a Chrome-trace JSON (load in
chrome://tracing or Perfetto) at exit; ``--profile-window DIR`` wraps the
run in a ``jax.profiler`` capture with GEMM-dispatch annotations;
``--metrics-dump F`` writes the final registry snapshot as JSON (the CI
artifact).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.precision import get_policy
from repro.launch import compile_cache
from repro.models import build_model
from repro.models.lm import LMCallOptions
from repro.obs import trace as obs_trace
from repro.obs.http import MetricsServer
from repro.runtime.server import LMServer, PerSlotLMServer, Request


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--policy", default="mirage")
    ap.add_argument("--engine", choices=("batched", "oracle"),
                    default="batched")
    ap.add_argument("--cache-layout", choices=("dense", "paged"),
                    default="dense",
                    help="paged = block-table KV pool (memory scales with "
                         "live tokens, not slots x cap)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="positions per KV block (paged layout)")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="block-pool size (default: slots * ceil(cap/block) "
                         "= no saving but never exhausts)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="stream prompts through decode ticks in chunks of "
                         "this many tokens (requires --cache-layout paged)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share matched prompt-prefix blocks copy-on-write "
                         "across slots (requires --cache-layout paged)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="first N prompt tokens identical across the "
                         "synthetic requests (makes --prefix-cache hit)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: self-draft this many tokens "
                         "per tick, verify in one step (paged + greedy)")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="data-parallel mesh axis: shard slots (and paged "
                         "block pools) over this many devices")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="tensor-parallel mesh axis: Megatron-shard the "
                         "GEMMs over this many devices")
    ap.add_argument("--block-placement", choices=("locality", "round_robin"),
                    default="locality",
                    help="paged-pool block placement under a data-sharded "
                         "mesh: prefer same-shard blocks per slot "
                         "(locality) or rotate blindly (round_robin, the "
                         "baseline the benchmark gates against)")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every (bucket, batch) prefill shape "
                         "plus tick/verify before traffic")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="overlap up to this many bucketed prefills with "
                         "decode on a worker thread (0 = synchronous)")
    ap.add_argument("--snr-db", type=float, default=None,
                    help="serve through the analog channel at this SNR "
                         "(use with --policy mirage_rns_noisy/mirage_rrns)")
    ap.add_argument("--noise-seed", type=int, default=0,
                    help="base seed for per-tick analog noise")
    ap.add_argument("--sample", action="store_true",
                    help="categorical sampling instead of greedy argmax")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics (Prometheus), /metrics.json and "
                         "/trace over HTTP on this port (0 = pick free)")
    ap.add_argument("--trace-export", default=None, metavar="FILE",
                    help="enable the span tracer and write Chrome-trace "
                         "JSON here at exit")
    ap.add_argument("--profile-window", default=None, metavar="LOGDIR",
                    help="capture a jax.profiler trace of the whole run "
                         "into this directory")
    ap.add_argument("--metrics-dump", default=None, metavar="FILE",
                    help="write the final metrics snapshot as JSON")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="fault-injection schedule, e.g. "
                         "'snr_drop@4:12:scale=30;worker_crash@2;"
                         "pool_exhaustion@3:9:blocks=16' (see "
                         "repro.runtime.faults)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the host-side fault sites (replays "
                         "bit-identically)")
    ap.add_argument("--guardian", action="store_true",
                    help="drain through the SNR guardian: verify-before-"
                         "commit windows over the analog-health counters, "
                         "escalating RRNS redundancy and falling back to "
                         "fp32 (requires --policy mirage_rrns + --snr-db)")
    ap.add_argument("--guardian-window", type=int, default=4,
                    help="decode ticks per guarded verify window")
    ap.add_argument("--ttl-s", type=float, default=None,
                    help="per-request end-to-end deadline: requests still "
                         "decoding past it retire as timed_out")
    ap.add_argument("--queue-ttl-s", type=float, default=None,
                    help="admission deadline: requests still queued past "
                         "it retire as timed_out")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="admission cap: reject submissions (with a "
                         "retry-after hint) past this queue depth")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="retry budget for fault-aborted requests")
    args = ap.parse_args(argv)
    if args.engine == "oracle" and args.sample:
        ap.error("--sample needs the batched engine (the per-slot oracle "
                 "is greedy-only)")
    if args.engine == "oracle" and (args.cache_layout != "dense" or
                                    args.prefill_chunk or args.prefix_cache
                                    or args.spec_k):
        ap.error("--cache-layout paged / --prefill-chunk / --prefix-cache / "
                 "--spec-k need the batched engine")
    if (args.prefix_cache or args.spec_k) and args.cache_layout != "paged":
        ap.error("--prefix-cache / --spec-k require --cache-layout paged")
    if args.spec_k and args.sample:
        ap.error("--spec-k verifies against greedy argmax; drop --sample")
    if args.engine == "oracle" and (args.mesh_data > 1 or args.mesh_model > 1
                                    or args.warmup or args.pipeline_depth):
        ap.error("--mesh-data/--mesh-model/--warmup/--pipeline-depth need "
                 "the batched engine")
    if args.engine == "oracle" and (args.chaos or args.guardian
                                    or args.max_queue_depth
                                    or args.ttl_s or args.queue_ttl_s):
        ap.error("--chaos/--guardian/--max-queue-depth/--ttl-s/--queue-ttl-s "
                 "need the batched engine")
    if args.guardian and args.policy != "mirage_rrns":
        ap.error("--guardian escalates RRNS redundancy; it needs "
                 "--policy mirage_rrns (plus --snr-db for a stochastic "
                 "channel worth guarding)")
    if args.guardian and args.pipeline_depth:
        ap.error("--guardian snapshots at window boundaries; drop "
                 "--pipeline-depth")

    if args.mesh_data > 1 or args.mesh_model > 1:
        need = args.mesh_data * args.mesh_model
        if len(jax.devices()) < need:
            ap.error(
                f"mesh {args.mesh_data}x{args.mesh_model} needs {need} "
                f"devices but only {len(jax.devices())} are visible; on a "
                f"CPU box set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return args


def build(args):
    """The model, its random weights (seed 0) and the engine, as ``main``
    serves them. Returns ``(cfg, server, injector)``."""
    mesh = None
    if args.mesh_data > 1 or args.mesh_model > 1:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(n_data=args.mesh_data,
                               n_model=args.mesh_model)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    overrides = {}
    if args.snr_db is not None:
        overrides.update(snr_db=args.snr_db, noise_seed=args.noise_seed)
    policy = get_policy(args.policy, **overrides)
    model = build_model(cfg, policy, LMCallOptions(q_chunk=32, kv_chunk=32))
    params = model.init(jax.random.PRNGKey(0))

    injector = None
    if args.chaos:
        from repro.runtime.faults import FaultInjector, FaultSchedule
        schedule = FaultSchedule.parse(args.chaos)
        injector = FaultInjector(schedule, seed=args.chaos_seed)
        print(f"chaos: {schedule.describe()} (seed {args.chaos_seed})")

    cap = args.prompt_len + args.max_tokens + 4
    if args.engine == "batched":
        server = LMServer(model, params, cap=cap, batch_slots=args.slots,
                          greedy=not args.sample,
                          cache_layout=args.cache_layout,
                          block_size=args.block_size,
                          n_blocks=args.n_blocks,
                          prefill_chunk=args.prefill_chunk,
                          prefix_cache=args.prefix_cache,
                          spec_k=args.spec_k,
                          mesh=mesh,
                          pipeline_depth=args.pipeline_depth,
                          block_placement=args.block_placement,
                          fault_injector=injector,
                          max_queue_depth=args.max_queue_depth,
                          default_ttl_s=args.ttl_s,
                          default_queue_ttl_s=args.queue_ttl_s,
                          max_retries=args.max_retries)
        if mesh is not None:
            print(f"mesh: data={args.mesh_data} x model={args.mesh_model} "
                  f"({len(mesh.devices.flat)} devices); allocator shards="
                  f"{server.alloc.n_shards if server.alloc else 1} "
                  f"placement={args.block_placement}")
        if args.warmup:
            w = server.warmup()
            print(f"warmup: {w['compiled']:.0f} shapes compiled in "
                  f"{w['seconds']:.1f}s")
    else:
        server = PerSlotLMServer(model, params, cap=cap,
                                 batch_slots=args.slots)
    return cfg, server, injector


def main(argv=None):
    compile_cache.enable()
    args = parse_args(argv)
    cfg, server, injector = build(args)
    if args.trace_export:
        obs_trace.configure(enabled=True)
    tracer = obs_trace.get_tracer()
    http_srv = None
    if args.metrics_port is not None:
        registry = getattr(server, "scheduler", None)
        registry = registry.registry if registry is not None else None
        http_srv = MetricsServer(port=args.metrics_port, registry=registry,
                                 tracer=tracer)
        http_srv.start()
        print(f"metrics at {http_srv.url}/metrics (json: /metrics.json, "
              f"trace: /trace)")

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size,
                          min(args.shared_prefix,
                              args.prompt_len)).astype(np.int32)
    t0 = time.perf_counter()
    for rid in range(args.requests):
        tail = rng.integers(0, cfg.vocab_size,
                            args.prompt_len - len(shared)).astype(np.int32)
        server.submit(Request(
            rid=rid,
            prompt=np.concatenate([shared, tail]),
            max_tokens=args.max_tokens))
    guardian = None
    if args.guardian:
        from repro.runtime.resilience import SNRGuardian
        guardian = SNRGuardian(server, window=args.guardian_window)
        drain = guardian.run_until_drained
    else:
        drain = server.run_until_drained
    profile_cm = (obs_trace.profile_window(args.profile_window, tracer)
                  if args.profile_window else contextlib.nullcontext())
    with profile_cm:
        finished = drain()
        finished = (server.scheduler.finished
                    if getattr(server, "scheduler", None) is not None
                    else finished)
    dt = time.perf_counter() - t0
    tot_toks = sum(len(r.tokens_out) for r in finished)
    # only requests that actually streamed have a TTFT (a chaos run can
    # time out / reject everything — the summary must not NaN)
    ttfts = [r.t_first_token - r.t_enqueue for r in finished
             if r.t_first_token > 0]
    mean_ttft_ms = float(np.mean(ttfts)) * 1e3 if ttfts else 0.0
    print(f"[{args.engine}] served {len(finished)} requests, {tot_toks} "
          f"tokens in {dt:.2f}s ({tot_toks / dt:.1f} tok/s); "
          f"mean TTFT {mean_ttft_ms:.1f}ms; "
          f"{server.metrics['ticks']} decode ticks")
    if getattr(server, "scheduler", None) is not None:
        by_status = {}
        for r in finished:
            by_status[r.status] = by_status.get(r.status, 0) + 1
        print(f"  terminal statuses: {by_status}")
    if guardian is not None:
        print(f"  guardian: level {guardian.level} "
              f"({len(guardian.transitions)} transitions)")
        for t in guardian.transitions:
            print(f"    {t}")
    if injector is not None and injector.log:
        print(f"  chaos log ({len(injector.log)} events):")
        for line in injector.log[:20]:
            print(f"    {line}")
    if getattr(server, "alloc", None) is not None:
        a = server.alloc
        print(f"  paged KV: block_size={a.block_size}, pool={a.n_blocks} "
              f"blocks, peak in use {a.peak_in_use} "
              f"({a.peak_in_use / a.n_blocks:.0%})")
        if a.n_shards > 1:
            print(f"  block locality ({a.placement}): "
                  f"{a.local_allocs} local / {a.spilled_allocs} spilled "
                  f"allocs; remote-gather fraction "
                  f"{a.remote_fraction():.2f}; free by shard "
                  f"{a.free_by_shard()}")
    m = server.metrics
    if args.prefix_cache:
        print(f"  prefix cache: {m['prefix_hits']} hits "
              f"({m['prefix_full_hits']} full), "
              f"{m['prefix_shared_blocks']} blocks shared")
    if args.spec_k:
        per = m["spec_accepted"] / max(m["spec_slot_ticks"], 1)
        print(f"  speculative k={args.spec_k}: {m['spec_accepted']} tokens "
              f"accepted over {m['spec_slot_ticks']} slot-ticks "
              f"({per:.2f}/tick)")
    if getattr(server, "scheduler", None) is not None:
        lat = server.scheduler.latency_summary()
        print(f"  TTFT p50/p95/p99: {lat['ttft_p50_s']*1e3:.1f}/"
              f"{lat['ttft_p95_s']*1e3:.1f}/{lat['ttft_p99_s']*1e3:.1f}ms; "
              f"TPOT p50/p95/p99: {lat['tpot_p50_s']*1e3:.1f}/"
              f"{lat['tpot_p95_s']*1e3:.1f}/{lat['tpot_p99_s']*1e3:.1f}ms")
        health = server.health_snapshot()
        if health:
            print(f"  analog health: {health}")
    for r in finished[:3]:
        print(f"  req {r.rid}: {r.tokens_out[:8]}...")

    if http_srv is not None:
        # self-scrape: prove the exposition endpoint round-trips before exit
        import urllib.request
        with urllib.request.urlopen(f"{http_srv.url}/metrics",
                                    timeout=5) as resp:
            n_series = sum(1 for ln in resp.read().decode().splitlines()
                           if ln and not ln.startswith("#"))
        print(f"  scraped {n_series} series from {http_srv.url}/metrics")
    if args.metrics_dump and getattr(server, "scheduler", None) is not None:
        with open(args.metrics_dump, "w") as f:
            json.dump(server.scheduler.registry.snapshot(), f, indent=2)
        print(f"  metrics snapshot -> {args.metrics_dump}")
    if args.trace_export:
        tracer.export(args.trace_export)
        print(f"  chrome trace ({tracer.n_recorded} spans) -> "
              f"{args.trace_export}")
    if http_srv is not None:
        http_srv.stop()
    if hasattr(server, "close"):
        server.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
