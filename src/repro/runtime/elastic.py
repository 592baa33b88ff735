"""Elastic scaling, preemption handling, straggler mitigation.

At 1000+-node scale the failure model is: nodes die (restore on a smaller
mesh), nodes come back (restore on a bigger mesh), the scheduler preempts
(SIGTERM -> checkpoint -> exit), and individual hosts straggle (flag + skip).
This module provides the host-side machinery; the numerical state lives in
checkpoint/checkpointer.py whose restore is already mesh-elastic.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Callable, Optional

import jax

from repro.checkpoint.checkpointer import Checkpointer


class PreemptionGuard:
    """SIGTERM/SIGINT -> set a flag; the train loop checkpoints and exits
    cleanly at the next step boundary instead of dying mid-write."""

    def __init__(self, install: bool = True):
        self.preempted = False
        self._orig = {}
        if install:
            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    self._orig[sig] = signal.signal(sig, self._handler)
                except ValueError:
                    pass  # non-main thread (tests)

    def _handler(self, signum, frame):
        self.preempted = True

    def uninstall(self):
        for sig, h in self._orig.items():
            signal.signal(sig, h)


@dataclasses.dataclass
class ElasticConfig:
    min_devices: int = 1
    reshard_on_restore: bool = True


def current_world() -> int:
    return jax.device_count()


def resize_serving_state(model, state, cap: int, new_slots: int,
                         keep: Optional[list] = None):
    """Rebuild a continuous-batching serving state with a different slot
    count (elastic up/down scale with offered load).

    ``state`` is the :class:`repro.runtime.server.LMServer` device pytree
    ({"cache": stacked cache, per-slot vectors...}). Slots listed in
    ``keep`` are compacted to the front of the new state; everything else
    starts empty (inactive). The caller remaps its host-side slot
    bookkeeping (and, for the paged layout, the block allocator via
    ``BlockAllocator.remap_slots``) to ``range(len(keep))``.

    Dense caches move through the ``models.lm`` gather/scatter helpers;
    paged caches keep their page POOLS untouched (block ids are stable
    under slot compaction) and only gather the per-slot leaves — ``idx``,
    the ``bt`` table rows and any dense recurrent state. Blocks shared
    between kept slots (copy-on-write prefix caching) stay shared: ids do
    not move, and ``remap_slots`` carries their refcounts; blocks whose
    only holders were dropped slots are freed (the server evicts them
    from its prefix index).
    """
    import jax.numpy as jnp

    from repro.models import lm as lm_helpers

    keep = list(keep or [])
    if len(keep) > new_slots:
        raise ValueError(f"{len(keep)} live slots do not fit in {new_slots}")
    cache = state["cache"]
    paged = "bt" in cache
    if paged:
        pool = next(k for k in lm_helpers.PAGE_POOL_LEAVES if k in cache)
        new_cache = model.init_cache(
            new_slots, cap, per_slot_idx=True, layout="paged",
            block_size=cache[pool].shape[2], n_blocks=cache[pool].shape[1])
        for k in lm_helpers.PAGE_POOL_LEAVES:
            if k in cache:
                new_cache[k] = cache[k]
    else:
        new_cache = model.init_cache(new_slots, cap, per_slot_idx=True)
    new_state = {"cache": new_cache}
    # "health" is the engine's pool-wide analog-fault accumulator dict —
    # not per-slot state; it survives a resize unchanged
    if "health" in state:
        new_state["health"] = state["health"]
    for k, v in state.items():
        if k in ("cache", "health"):
            continue
        new_state[k] = jnp.zeros((new_slots,) + v.shape[1:], v.dtype)
    if keep:
        dst = jnp.arange(len(keep), dtype=jnp.int32)
        src = jnp.asarray(keep, jnp.int32)
        if paged:
            for k, v in new_cache.items():
                if k in lm_helpers.PAGE_POOL_LEAVES:
                    continue
                old = cache[k]
                if lm_helpers.cache_slot_axis(k) == 0:
                    new_cache[k] = v.at[dst].set(old[src])
                else:
                    new_cache[k] = v.at[:, dst].set(old[:, src])
            new_state["cache"] = new_cache
        else:
            new_state["cache"] = lm_helpers.cache_insert(
                new_cache, lm_helpers.cache_extract(cache, src), dst)
        for k, v in state.items():
            if k in ("cache", "health"):
                continue
            new_state[k] = new_state[k].at[dst].set(v[src])
    return new_state


def resize_block_pool(state, allocator, new_n_blocks: int):
    """Elastic paged-pool resize: compact live blocks to the front of a
    pool of ``new_n_blocks`` (grow under admission pressure, shrink after a
    long-context burst retires). ``allocator`` is the server's
    :class:`repro.runtime.paging.BlockAllocator` — its ``resize_pool``
    renumbers the live blocks and rewrites every table; this moves the page
    ARRAYS to match. Refcounts move with the renumbering, so blocks shared
    across slots stay shared at their new ids. Under a sharded allocator
    the compaction is shard-preserving, so the renumbering is NOT simple
    sorted order — the explicit ``(old_ids, new_ids)`` map is returned
    alongside the new state so the caller can remap its prefix index by
    the same permutation. Raises if the live blocks don't fit the new
    pool."""
    import jax.numpy as jnp

    from repro.models import lm as lm_helpers

    old_ids, new_ids = allocator.resize_pool(new_n_blocks)
    cache = dict(state["cache"])
    for k in lm_helpers.PAGE_POOL_LEAVES:
        if k not in cache:
            continue
        v = cache[k]
        nv = jnp.zeros(v.shape[:1] + (int(new_n_blocks),) + v.shape[2:],
                       v.dtype)
        if len(old_ids):
            nv = nv.at[:, jnp.asarray(new_ids)].set(
                v[:, jnp.asarray(old_ids)])
        cache[k] = nv
    cache["bt"] = jnp.asarray(allocator.tables)
    allocator.dirty = False
    return dict(state, cache=cache), old_ids, new_ids


def elastic_restore(ckpt: Checkpointer, abstract_state, shardings,
                    step: Optional[int] = None):
    """Restore the latest checkpoint onto the CURRENT mesh. Because leaves are
    stored unsharded (host numpy) and re-device_put with today's shardings,
    this works across device-count changes (elastic up/down scale)."""
    return ckpt.restore(abstract_state, step=step, shardings=shardings)


class StragglerMitigator:
    """Tracks per-step wall time; when a step exceeds ``factor`` x EMA more
    than ``patience`` consecutive times, fires ``on_straggle`` (at cluster
    scale: re-shard around the slow host / raise for the controller).

    On a single host this demotes to monitoring + logging, but the hook is
    what a production controller subscribes to."""

    def __init__(self, factor: float = 2.0, patience: int = 3,
                 on_straggle: Optional[Callable[[int, float], None]] = None):
        self.factor = factor
        self.patience = patience
        self.on_straggle = on_straggle or (lambda step, dt: None)
        self.ema = 0.0
        self.beta = 0.9
        self.consecutive = 0
        self.events = 0

    def record(self, step: int, dt: float) -> bool:
        slow = self.ema > 0 and dt > self.factor * self.ema
        if slow:
            self.consecutive += 1
            if self.consecutive >= self.patience:
                self.events += 1
                self.on_straggle(step, dt)
                self.consecutive = 0
        else:
            self.consecutive = 0
        self.ema = dt if self.ema == 0 else self.beta * self.ema + (1 - self.beta) * dt
        return slow


def fault_tolerant_train_loop(model, train_cfg, state, data, n_steps: int,
                              ckpt: Checkpointer, ckpt_every: int = 50,
                              log_fn=print, guard: Optional[PreemptionGuard] = None,
                              straggler: Optional[StragglerMitigator] = None):
    """Training loop with preemption-safe checkpointing + data-state capture.

    The data pipeline state is stored in checkpoint metadata, so a restart
    resumes on exactly the batch the failed run would have consumed next."""
    import jax as _jax
    from repro.runtime.trainer import make_train_step

    # the state is donated: save_async copies it to host before returning,
    # so no checkpoint reads a buffer the next step has reused
    step_fn = _jax.jit(make_train_step(model, train_cfg), donate_argnums=0)
    guard = guard or PreemptionGuard(install=False)
    straggler = straggler or StragglerMitigator()
    metrics = {}
    from repro.obs import trace as obs_trace
    tr = obs_trace.get_tracer()
    for _ in range(n_steps):
        with tr.span("train.data_next"):
            batch = next(data)
        t0 = time.perf_counter()
        with tr.span("train.step"):
            state, metrics = step_fn(state, batch)
        with tr.span("train.host_sync"):
            _jax.block_until_ready(metrics["loss"])
        step = int(state["step"])
        straggler.record(step, time.perf_counter() - t0)
        if ckpt_every and step % ckpt_every == 0:
            ckpt.save_async(state, step, metadata={"data": data.state()}
                            if hasattr(data, "state") else None)
        if guard.preempted:
            log_fn(f"preempted at step {step}: checkpointing and exiting")
            ckpt.wait()
            ckpt.save(state, step, metadata={"data": data.state()}
                      if hasattr(data, "state") else None)
            break
    ckpt.wait()
    return state, metrics
