#!/usr/bin/env python3
"""Chip smoke test: the repo's main paths on a TPU, at full published width.

    python3 chip_smoke.py             # one chip: phases 1-4 below
    python3 chip_smoke.py --chips 4   # four chips: meshed serving parity only

Model: qwen2-0.5b at its published widths (24 layers, d_model 896, 14 query
/ 2 KV heads, d_ff 4864, vocab 151,936), whole on one chip, random weights
from seed 0. All phases run in this one process, which holds the chip.

1. Device: fails unless ``jax.devices()[0].platform == "tpu"``.
2. Train: ``repro.launch.train.main`` under ``mirage`` for 3 steps at batch
   4 x seq 512. Every loss must be finite, and the step-0 loss must be
   within 1 % of the ``fp32`` policy's step-0 loss on the same batch and
   init (run with f32 matmuls at "highest" precision). The paper claims
   FP32-level accuracy for BFP(4, 16).
3. Train on the Pallas path: one compiled step under ``mirage_rrns`` with
   ``use_pallas=True`` at batch 1 x seq 64, against the jnp ``mirage_rrns``
   loss on the same batch and init. The jnp path cannot hold the 151,936-
   column LM head in 16 GB (its decode materializes every residue
   intermediate), so its loss is computed forward-only with the head split
   into vocabulary blocks; output columns are independent, so the split
   changes no value. Residues and decodes are exact integers on both
   paths, so the two are expected bit-identical; the check allows 1e-5
   relative for an f32 cross-group sum that XLA may order differently in
   the two programs, and prints whether they were identical.
4. Serve: ``LMServer`` built by ``repro.launch.serve.build``, paged cache,
   4 slots, 4 requests of 128 prompt tokens and 16 new tokens, and request
   0's greedy tokens checked against a teacher-forced full forward over
   prompt + generated tokens. First under ``fp32`` with f32 matmuls at
   "highest" precision: every served token's logit within 1e-3 of the
   forward's top, which checks the engine (prefill, paged cache, decode
   positions) to rounding. Then under ``mirage``: at least 4 of the 16 are
   the forward's argmax and every gap is within 1.0. The decode and the
   forward are two roundings of one BFP computation: on the TPU a GEMM's
   f32 rounding depends on its shape, a one-ulp difference can flip a 4-bit
   mantissa, and over 24 random-weight layers the flips cascade. On a v5e 8
   of 16 matched (11 at "highest" precision), the largest gap 0.35; mirage
   logits differ from fp32 ones by up to about 1.0 (CPU, 12 layers). A
   cache or position fault makes the tokens unrelated to the forward:
   about 16/151,936 exact.

With ``--chips 4`` only this runs: the same 4 requests on ``LMServer`` over
a data=2 x model=2 mesh, then on one device, in this process; the greedy
streams must be identical, and params and KV cache must be spread over all
four devices.

The last line of stdout is ``{"ok": true, "device": {...}}``; everything
else (losses, memory, host wall-clock times from this single run, which are
not benchmark results) comes before it. Any failure exits non-zero without
that line.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"
SEED = 0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def device_check(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    check(d.platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {d.platform!r}")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def device_memory(label: str) -> None:
    """Bytes in use and the peak on the first device, where reported."""
    import gc

    import jax

    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"{label}: device bytes_in_use {stats.get('bytes_in_use')} "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def phase_train(reduced: bool = False) -> None:
    import jax

    from repro.launch import train

    argv = ["--arch", ARCH, "--batch", "4", "--seq", "512",
            "--seed", str(SEED)] + (["--reduced"] if reduced else [])
    print("== phase 2: train (mirage, 3 steps, batch 4 x seq 512)")
    rep = train.main(argv + ["--policy", "mirage", "--steps", "3"])
    losses = rep["losses"]
    print(f"mirage losses: {losses}")
    print(f"mirage compile {rep['compile_seconds']:.1f}s, step times "
          f"{[round(t, 4) for t in rep['step_seconds']]}s "
          f"(host wall-clock)")
    print(f"mirage step memory bytes: {rep['memory']}")
    check(len(losses) == 3 and all(math.isfinite(v) for v in losses),
          f"non-finite mirage loss: {losses}")
    print("== phase 2: fp32 reference step 0 (highest matmul precision)")
    with jax.default_matmul_precision("highest"):
        ref = train.main(argv + ["--policy", "fp32", "--steps", "1"])
    l0, r0 = losses[0], ref["losses"][0]
    rel = abs(l0 - r0) / abs(r0)
    print(f"step-0 loss: mirage {l0!r} fp32 {r0!r} rel diff {rel:.3e} "
          f"(tolerance 1e-2)")
    check(math.isfinite(r0) and rel <= 1e-2,
          f"mirage step-0 loss {l0} is not within 1% of fp32 {r0}")


def _jnp_rrns_loss(model, params, batch, ce_chunk: int, block: int):
    """The jnp ``mirage_rrns`` loss, forward only, with the tied LM head
    applied to ``block``-row slices of the embedding table."""
    import jax.numpy as jnp

    from repro.models.lm import chunked_ce

    check(model.cfg.tie_embeddings, "the split head assumes tied embeddings")
    h, aux, _ = model.forward_hidden(params, batch["tokens"])
    B, L, d = h.shape
    emb = params["embed"]["emb"]

    def head(hh):
        return jnp.concatenate(
            [model._head(dict(params, embed={"emb": emb[v:v + block]}), hh)
             for v in range(0, emb.shape[0], block)], axis=-1)

    ce = chunked_ce(h.reshape(B * L, d), batch["labels"].reshape(B * L),
                    head, ce_chunk)
    return ce + model.cfg.router_aux_loss * aux / max(model.cfg.n_layers, 1)


def phase_pallas(reduced: bool = False) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.core.precision import get_policy, resolve_interpret
    from repro.data.pipeline import SyntheticLM, SyntheticLMConfig
    from repro.models import build_model
    from repro.models.lm import LMCallOptions
    from repro.runtime.trainer import (init_train_state, make_train_step,
                                       memory_summary)

    print("== phase 3: mirage_rrns train step, Pallas vs jnp "
          "(batch 1 x seq 64)")
    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduced()
    ce_chunk = 8
    opts = LMCallOptions(q_chunk=64, kv_chunk=64, remat=True,
                         ce_chunk=ce_chunk)
    batch = next(iter(SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=64, batch_size=1, seed=SEED))))
    batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
    key = jax.random.PRNGKey(SEED)

    pol_j = get_policy("mirage_rrns")
    model_j = build_model(cfg, pol_j, opts)
    params = model_j.init(key)
    t0 = time.perf_counter()
    loss_j = float(jax.jit(
        lambda p, b: _jnp_rrns_loss(model_j, p, b, ce_chunk, 8192))(
            params, batch))
    print(f"jnp mirage_rrns loss {loss_j!r} "
          f"({time.perf_counter() - t0:.1f}s host wall-clock incl. compile)")
    del params

    pol_p = get_policy("mirage_rrns", use_pallas=True)
    interpreted = resolve_interpret(pol_p.interpret)
    print(f"Pallas kernels interpreted: {interpreted}")
    check(reduced or not interpreted,
          "Pallas kernels would run interpreted on the chip")
    model_p = build_model(cfg, pol_p, opts)
    tc = TrainConfig(policy=pol_p, optimizer="adamw", lr=1e-3, seed=SEED)
    state = init_train_state(model_p, tc, key)
    t0 = time.perf_counter()
    step = jax.jit(make_train_step(model_p, tc), donate_argnums=0).lower(
        state, batch).compile()
    print(f"Pallas step compiled in {time.perf_counter() - t0:.1f}s "
          f"(host wall-clock); memory bytes: {memory_summary(step)}")
    hlo = step.as_text()
    check(reduced or "tpu_custom_call" in hlo,
          "the Pallas train step holds no Mosaic kernel")
    t0 = time.perf_counter()
    state, metrics = step(state, batch)
    loss_p = float(metrics["loss"])
    print(f"Pallas mirage_rrns step loss {loss_p!r} "
          f"({time.perf_counter() - t0:.3f}s host wall-clock)")
    rel = abs(loss_p - loss_j) / abs(loss_j)
    print(f"Pallas vs jnp: bit-identical {loss_p == loss_j}, rel diff "
          f"{rel:.3e} (tolerance 1e-5)")
    check(np.isfinite(loss_p) and rel <= 1e-5,
          f"Pallas rrns loss {loss_p} vs jnp {loss_j}")


def _serve_args(policy: str, extra=()):
    from repro.launch import serve

    return serve.parse_args(
        ["--arch", ARCH, "--policy", policy, "--cache-layout", "paged",
         "--slots", "4", "--requests", "4", "--prompt-len", "128",
         "--max-tokens", "16"] + list(extra))


def _prompts(vocab: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, 128).astype(np.int32) for _ in range(4)]


def _serve(server, prompts):
    """Submit the 4 requests, drain, return ({rid: tokens}, seconds)."""
    from repro.runtime.server import Request

    for rid, p in enumerate(prompts):
        server.submit(Request(rid=rid, prompt=p, max_tokens=16))
    t0 = time.perf_counter()
    server.run_until_drained()
    dt = time.perf_counter() - t0
    out = {r.rid: [int(t) for t in r.tokens_out]
           for r in server.scheduler.finished}
    check(sorted(out) == [0, 1, 2, 3] and
          all(len(v) == 16 for v in out.values()),
          f"serving did not complete 4 x 16 tokens: "
          f"{ {k: len(v) for k, v in out.items()} }")
    return out, dt


def _served_vs_forward(policy: str, extra):
    """Serve the 4 requests under ``policy``; return request 0's exact-
    argmax count and the gaps of its tokens below a teacher-forced full
    forward's top logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve

    cfg, server, _ = serve.build(_serve_args(policy, extra))
    prompts = _prompts(cfg.vocab_size)
    toks, dt = _serve(server, prompts)
    print(f"{policy}: served 4 x 16 tokens in {dt:.2f}s host wall-clock "
          f"incl. compile; {server.metrics['ticks']} decode ticks")
    print(f"{policy}: request 0 tokens {toks[0]}")
    seq = np.concatenate([prompts[0], np.asarray(toks[0][:-1], np.int32)])
    logits = jax.jit(lambda p, t: server.model.forward(p, t)[0])(
        server.params, jnp.asarray(seq)[None])
    server.close()
    ref = np.asarray(logits[0, len(prompts[0]) - 1:], np.float32)
    gap = ref.max(axis=-1) - ref[np.arange(16), toks[0]]
    exact = int(np.sum(ref.argmax(axis=-1) == np.asarray(toks[0])))
    print(f"{policy}: teacher-forced forward {exact}/16 exact argmax; gaps "
          f"below the top logit {np.round(gap, 5).tolist()}")
    return exact, gap


def phase_serve(reduced: bool = False) -> None:
    import jax
    import numpy as np

    print("== phase 4: serve (paged, 4 slots, 4 x (128 + 16))")
    extra = ["--reduced"] if reduced else []
    with jax.default_matmul_precision("highest"):
        exact, gap = _served_vs_forward("fp32", extra)
    check(bool(np.all(gap <= 1e-3)),
          f"fp32 engine disagrees with its forward: gaps {gap.tolist()}")
    exact, gap = _served_vs_forward("mirage", extra)
    check(exact >= 4 and bool(np.all(gap <= 1.0)),
          f"mirage engine disagrees with its forward: {exact}/16 exact, "
          f"gaps {gap.tolist()}")


def _spread(tree, n_dev: int, what: str) -> None:
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if isinstance(x, jax.Array)]
    devs = set()
    split = 0
    for x in leaves:
        devs |= set(x.sharding.device_set)
        if x.sharding.shard_shape(x.shape) != x.shape:
            split += 1
    print(f"{what}: {len(leaves)} arrays over {len(devs)} devices, "
          f"{split} partitioned")
    check(len(devs) == n_dev and split > 0,
          f"{what} is not spread over the {n_dev} devices")


def phase_mesh(reduced: bool = False) -> None:
    import jax

    from repro.launch import serve

    print("== four chips: LMServer on data=2 x model=2 vs one device")
    extra = ["--reduced"] if reduced else []
    args = _serve_args("mirage",
                       extra + ["--mesh-data", "2", "--mesh-model", "2"])
    cfg, meshed, _ = serve.build(args)
    _spread(meshed._exec_params, 4, "params")
    _spread(meshed.state, 4, "serving state (KV pool, tables)")
    prompts = _prompts(cfg.vocab_size)
    toks_m, dt_m = _serve(meshed, prompts)
    for d in jax.devices()[:4]:
        stats = d.memory_stats() or {}
        print(f"  {d}: bytes_in_use {stats.get('bytes_in_use')}")
    meshed.close()
    del meshed
    _, single, _ = serve.build(_serve_args("mirage", extra))
    toks_1, dt_1 = _serve(single, prompts)
    single.close()
    print(f"meshed {dt_m:.2f}s, one device {dt_1:.2f}s host wall-clock "
          f"incl. compile")
    for rid in range(4):
        print(f"request {rid}: meshed {toks_m[rid]}")
        print(f"request {rid}: single {toks_1[rid]}")
    check(toks_m == toks_1,
          "data=2 x model=2 greedy streams differ from one device's")
    print("token parity: meshed == one device for all 4 requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the meshed-serving parity phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout "
             f"of the repository")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch import compile_cache

    print(f"compile cache: {compile_cache.enable()}")
    device = device_check(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh()
    else:
        phase_train()
        device_memory("after train")
        phase_pallas()
        device_memory("after Pallas step")
        phase_serve()
        device_memory("after serving")
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s host "
          f"wall-clock")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
