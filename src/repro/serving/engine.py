"""Serving engine: slotted KV caches, jit'd chunked-prefill + batched decode
steps, iteration-level scheduling (Orca-style continuous batching).

The engine owns a [max_batch, max_len] cache; requests are admitted into
slots, prefilled (whole-prompt or chunk-at-a-time, per the scheduler), then
decoded together — one jit'd ``decode_step`` over all active slots per
iteration, exactly the merged-QKV/FFN + split-attention execution pattern
the DSE layer models.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..models.transformer import ModelConfig, decode_step, extend, init_cache
from .scheduler import (
    Scheduler,
    ServeRequest,
    admit_arrivals,
    complete_prefill,
    retire_finished,
    try_admit,
)


@dataclass
class IterationStats:
    it: int
    n_prefill_tokens: int
    n_decode: int
    seconds: float
    # occupancy / pressure gauges (0 where a backend has no such notion)
    queue_depth: int = 0        # requests admitted but not yet scheduled
    slots_used: int = 0         # batch slots occupied after the iteration
    blocks_used: int = 0        # KV blocks resident (paged service only)
    blocked_admissions: int = 0  # admissions refused for lack of blocks
    preempts: int = 0
    evictions: int = 0


@dataclass
class RunResult:
    """``ServingEngine.run`` outcome. Unpacks like the historical
    ``(finished, stats)`` tuple; additionally carries the requests still in
    flight when the iteration budget ran out (previously dropped silently).
    """

    finished: list[ServeRequest]
    stats: list[IterationStats]
    unfinished: list[ServeRequest] = field(default_factory=list)
    truncated: bool = False

    def __iter__(self):
        yield self.finished
        yield self.stats


class ServingEngine:
    def __init__(self, params, cfg: ModelConfig, max_batch: int = 8,
                 max_len: int = 512, impl: str = "xla", enc_out=None,
                 cache_dtype=jnp.float32):
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.enc_out = enc_out
        self.cache = init_cache(cfg, max_batch, max_len, dtype=cache_dtype)
        self.free = list(range(max_batch))
        self.impl = impl

        def _decode(params, tokens, cache, active):
            logits, cache = decode_step(params, cfg, tokens, cache,
                                        enc_out=enc_out, impl=impl,
                                        active=active)
            return jnp.argmax(logits, -1), cache

        self._decode = jax.jit(_decode)
        # chunk lengths are bucketed to powers of two (padding masked out by
        # `length`) and the slot rides as a traced scalar, so the jit cache
        # holds one entry per bucket size — not one per (slot, chunk length)
        self._extend = jax.jit(partial(self._extend_impl))

    def _extend_impl(self, params, tokens, cache, slot, length):
        """Run a chunk for one slot: gather row -> extend -> scatter back.
        ``tokens`` is padded to its bucket; ``slot``/``length`` are traced
        scalars."""
        row = jax.tree.map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, 0), cache)
        logits, row = extend(params, self.cfg, tokens[None, :], row,
                             enc_out=None if self.enc_out is None
                             else self.enc_out[:1], impl=self.impl,
                             length=length)

        def put(c, r):
            starts = (slot,) + (0,) * (c.ndim - 1)
            return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), starts)

        cache = jax.tree.map(put, cache, row)
        return jnp.argmax(logits, -1)[0], cache

    @staticmethod
    def _bucket(n: int) -> int:
        """Smallest power of two >= n."""
        return 1 << max(0, n - 1).bit_length()

    def run(self, requests: list[ServeRequest], scheduler: Scheduler,
            max_iters: int = 10_000):
        for r in requests:
            if r.prefill_done and r.slot is None:
                # warm (decode-resident) requests are a pure-rollout
                # modeling device: the engine has no KV state for a prompt
                # it never ran, so admitting one would decode over a stale
                # or zeroed cache and silently emit garbage
                raise ValueError(
                    f"request {r.rid} is already prefilled but holds no "
                    "cache slot; the dense engine cannot serve warm "
                    "requests — use repro.core.streams.rollout for pure "
                    "simulation, or AsyncLLMService (which prefaults the "
                    "warm context into its paged cache at admission)")
        pending = sorted(requests, key=lambda r: r.arrived_iter)
        waiting: list[ServeRequest] = []
        running: list[ServeRequest] = []
        finished: list[ServeRequest] = []
        stats: list[IterationStats] = []
        telemetry.bump("engine_runs")
        it = 0
        while (pending or waiting or running) and it < max_iters:
            admit_arrivals(pending, waiting, running, self.free, it)
            queue_depth = len(waiting)
            plan = scheduler.plan(waiting, running, len(self.free))
            t0 = time.perf_counter()
            n_prefill_tok = 0

            for req, chunk_len in plan.prefill:
                had_slot = req.slot is not None
                if not try_admit(req, self.free):
                    continue
                if not had_slot:
                    self._reset_slot(req.slot)
                chunk = req.prompt[req.prefilled: req.prefilled + chunk_len]
                n = len(chunk)
                padded = np.zeros((self._bucket(n),), np.int32)
                padded[:n] = chunk
                tok, self.cache = self._extend(
                    self.params, jnp.asarray(padded), self.cache,
                    jnp.asarray(req.slot, jnp.int32),
                    jnp.asarray(n, jnp.int32))
                req.prefilled += n
                n_prefill_tok += n
                if req.prefill_done:
                    req.generated.append(int(tok))
                    complete_prefill(req, it, waiting, running)

            if plan.decode:
                toks = np.zeros((self.max_batch,), np.int32)
                active = np.zeros((self.max_batch,), bool)
                for r in plan.decode:
                    toks[r.slot] = r.generated[-1]
                    active[r.slot] = True
                new_toks, self.cache = self._decode(
                    self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(active))
                new_toks = np.asarray(new_toks)
                for r in plan.decode:
                    r.generated.append(int(new_toks[r.slot]))

            retire_finished(running, finished, self.free, it)

            stats.append(IterationStats(
                it, n_prefill_tok, len(plan.decode),
                time.perf_counter() - t0,
                queue_depth=queue_depth,
                slots_used=self.max_batch - len(self.free)))
            telemetry.bump("iterations")
            telemetry.bump("prefill_tokens", n_prefill_tok)
            telemetry.bump("decode_tokens", len(plan.decode))
            telemetry.high_water("peak_slots_used",
                                 self.max_batch - len(self.free))
            telemetry.high_water("peak_queue_depth", queue_depth)
            it += 1

        unfinished = pending + waiting + running
        if unfinished:
            telemetry.bump("truncated_runs")
            telemetry.bump("unfinished_requests", len(unfinished))
            warnings.warn(
                f"engine run truncated at max_iters={max_iters} with "
                f"{len(unfinished)} request(s) still in flight — they are "
                "reported in RunResult.unfinished, not silently dropped",
                stacklevel=2)
        return RunResult(finished, stats, unfinished=unfinished,
                         truncated=bool(unfinished))

    def _reset_slot(self, slot: int):
        """Reset a slot for a fresh request: live length to zero plus the
        (tiny) recurrent state rows. KV contents are deliberately left
        stale — every attention path masks reads by ``len``, so zeroing
        [max_len, heads, dim] per layer on every admission bought nothing
        but a full-cache write."""
        new_cache = []
        for layer in self.cache:
            d = dict(layer)
            d["len"] = layer["len"].at[slot].set(0)
            if "state" in layer:
                d["state"] = layer["state"].at[slot].set(
                    jnp.zeros_like(layer["state"][slot]))
            new_cache.append(d)
        self.cache = new_cache


def summarize(finished: list[ServeRequest], stats: list[IterationStats],
              unfinished: list[ServeRequest] | None = None):
    total_s = sum(s.seconds for s in stats)
    out_toks = sum(len(r.generated) for r in finished)
    ttft = [r.first_token_iter - r.arrived_iter for r in finished
            if r.first_token_iter is not None]
    n_it = len(stats)
    return {
        "requests": len(finished),
        "unfinished": len(unfinished) if unfinished is not None else 0,
        "iterations": n_it,
        "output_tokens": out_toks,
        "total_seconds": total_s,
        "tokens_per_second": out_toks / total_s if total_s else 0.0,
        "mean_ttft_iters": float(np.mean(ttft)) if ttft else 0.0,
        "mean_queue_depth": float(np.mean([s.queue_depth for s in stats]))
        if n_it else 0.0,
        "mean_slots_used": float(np.mean([s.slots_used for s in stats]))
        if n_it else 0.0,
        "peak_blocks_used": max((s.blocks_used for s in stats), default=0),
        "blocked_admissions": sum(s.blocked_admissions for s in stats),
        "preempts": sum(s.preempts for s in stats),
        "evictions": sum(s.evictions for s in stats),
    }
