"""Driver of the served-path cells.

Set-up makes the weights from the seed (the configuration's family,
``bench/family.py``), builds ``AsyncLLMService`` over the
paged KV pool with the cell's configuration, and compiles every prefill
chunk bucket and every decode batch bucket the traffic can reach. Then one
``serve`` call runs the cell's mix (``bench/traffic.py``): the first
``warmup_s`` seconds bring the service to steady occupancy and count as
set-up, the next ``--seconds`` are the window, and at its close the harness
stops the serve (its clock raises at the next iteration): what a cell
measures is the window, and a drain of long requests would only stretch
the run.

The harness's spans sit around the service's prefill-chunk and decode
calls, each of which ends on host-visible tokens. After the window, once
the KV pools are freed, the family's plain reference (for a dense decoder
``bench/reference/qwen.py``) runs over a seeded sample of the requests
served, the longest among them, and the widest gap by which a served
token's logit lies below the reference's best is compared with its limit.

A traced run also keeps, for the per-layer readers, the live context of
every decode step that ran inside the traced window and the ``op_name``
path of every instruction of the decode entries (their compiled HLO, read
after the window), so that a reader can take one kernel's device time
from the trace and its least work from the steps.
"""
from __future__ import annotations

import asyncio
import gc
import time
from functools import partial

import numpy as np

from bench import family, harness, trace, traffic, work

TRACE_LEAD_S = 3.0      # the profiler starts this long before the window


class WindowClosed(Exception):
    """Raised by the service's clock at the first iteration after the
    window has closed."""


class DueClock:
    """Service clock that releases request ``i`` at its due time and, once
    ``close_at`` has passed, stops the serve. Request ids double as arrival
    indices, so the service's own iteration gate on ``arrived_iter`` never
    holds a request that is due."""

    deterministic = False

    def __init__(self, due_s):
        self.due = list(due_s)
        self.t0 = time.perf_counter()
        self.close_at = None

    @property
    def now(self) -> float:
        return time.perf_counter() - self.t0

    async def sleep_until(self, i) -> None:
        dt = self.t0 + self.due[int(i)] - time.perf_counter()
        if dt > 0:
            await asyncio.sleep(dt)

    def advance(self, _t) -> None:
        if self.close_at is not None and time.perf_counter() >= self.close_at:
            raise WindowClosed


def model_config(cfg: dict):
    from repro.models.transformer import ModelConfig

    return ModelConfig(name=cfg["name"], **cfg["model"])


def build_service(cfg: dict, params, n_requests: int, clock):
    import jax.numpy as jnp

    from repro.serving import AsyncLLMService, ServiceConfig

    sv = cfg["service"]
    return AsyncLLMService(
        params, model_config(cfg),
        ServiceConfig(max_batch=sv["max_batch"], max_len=sv["max_len"],
                      block_len=sv["block_len"], num_blocks=sv["num_blocks"],
                      queue_depth=n_requests + 1, max_iters=10 ** 9),
        clock=clock, cache_dtype=getattr(jnp, cfg["dtype"]))


def buckets(n: int) -> list:
    out, b = [], 1
    while True:
        out.append(b)
        if b >= n:
            return out
        b *= 2


def decode_args(svc, b: int) -> tuple:
    """Arguments of the decode entry of batch bucket ``b``: every lane a
    padding lane."""
    import jax.numpy as jnp

    kv = svc.kv
    return (svc.params, jnp.zeros((b,), jnp.int32), kv.pools,
            jnp.zeros((b, kv.blocks_per_seq), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.full((b,), kv.scratch_slot, jnp.int32))


def decode_op_names(svc, batch_buckets) -> dict:
    """{module: {instruction: op_name path}} of the decode entries of these
    batch buckets (those ``warm_entries`` compiled), read from their
    compiled HLO (the persistent cache holds each program; run in the
    serve's matmul precision, as they were)."""
    out = {}
    for b in sorted(batch_buckets):
        compiled = svc._decode_entry(b).lower(*decode_args(svc, b)).compile()
        module, names = trace.hlo_op_names(compiled.as_text())
        out[module] = names
    return out


def warm_entries(svc, chunk: int, max_batch: int) -> int:
    """Compile (or load) every prefill chunk bucket and decode batch bucket
    the cell can reach; returns how many."""
    import jax
    import jax.numpy as jnp

    kv = svc.kv
    n = 0
    for c in buckets(chunk):
        fn = svc._prefill_entry(c)
        # keep only the token: the new pools (as large as the old) go at once
        tok = fn(svc.params, jnp.zeros((c,), jnp.int32), kv.pools,
                 jnp.zeros((kv.blocks_per_seq,), jnp.int32),
                 jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                 jnp.asarray(1, jnp.int32))[0]
        jax.block_until_ready(tok)
        n += 1
    for b in buckets(max_batch):
        toks = svc._decode_entry(b)(*decode_args(svc, b))[0]
        jax.block_until_ready(toks)
        n += 1
    return n


class ServeProbe:
    """Wraps the service's prefill-chunk and decode calls: spans, tokens,
    FLOPs (``token_flops(ctx)`` of one token attending ``ctx`` positions)
    and live context with their end times, the programs lowered by the
    window's edges, and the traced part of the window."""

    def __init__(self, svc, spans, token_flops, tracer=None, alter=None,
                 compiles=None):
        self.svc, self.spans, self.token_flops = svc, spans, token_flops
        self.tracer, self.alter, self.compiles = tracer, alter, compiles
        self.prefills: list = []     # (t0, t1, n, start, completed)
        # (t0, t1, n, flops, positions attended: sum of len + 1 per lane)
        self.decodes: list = []
        self.lowered = [None, None]  # programs lowered by window open, close
        self.trace_from = self.trace_to = self.window_to = None
        self._tracing = "off"        # off -> on -> window -> done
        self._prefill, self._decode = svc._run_prefill_chunk, svc._run_decode
        svc._run_prefill_chunk = self.prefill
        svc._run_decode = self.decode

    def detach(self):
        """Drop every reference to the service (and its KV pools)."""
        self.svc = self._prefill = self._decode = None

    def _tick(self, t):
        """At call ends: count programs lowered by the window's edges; start
        the profiler a little before the window (starting takes a moment),
        mark the window's open, and stop after ``trace_to``."""
        if self.compiles is not None:
            for i, edge in enumerate((self.trace_from, self.window_to)):
                if self.lowered[i] is None and edge is not None and t >= edge:
                    self.lowered[i] = self.compiles.count
        if self.tracer is None or self.trace_from is None:
            return
        if self._tracing == "off" and t >= self.trace_from - TRACE_LEAD_S:
            self.tracer.start()
            self._tracing = "on"
        if self._tracing == "on" and t >= self.trace_from:
            self.tracer.open_window()
            self._tracing = "window"
        if self._tracing == "window" and t >= self.trace_to:
            self.finish()

    def finish(self):
        if self._tracing in ("on", "window"):
            self.tracer.stop()
        self._tracing = "done"
        if self.compiles is not None:    # the serve ended before an edge
            self.lowered = [self.compiles.count if v is None else v
                            for v in self.lowered]

    def prefill(self, req, chunk_len):
        start = req.prefilled
        with self.spans.span("bench.prefill"):
            t0 = time.perf_counter()
            tok = self._prefill(req, chunk_len)
            t1 = time.perf_counter()
        if self.alter is not None:
            tok = self.alter(req, tok)
        self.prefills.append((t0, t1, req.prefilled - start, start,
                              req.prefill_done))
        self._tick(t1)
        return tok

    def decode(self, batch):
        lens = [int(self.svc.kv.lens_np[r.slot]) for r in batch]
        with self.spans.span("bench.decode"):
            t0 = time.perf_counter()
            self._decode(batch)
            t1 = time.perf_counter()
        if self.alter is not None:
            for r in batch:
                r.generated[-1] = self.alter(r, r.generated[-1])
        flops = sum(self.token_flops(ln + 1) for ln in lens)
        self.decodes.append((t0, t1, len(batch), flops,
                             sum(ln + 1 for ln in lens)))
        self._tick(t1)


def traced_decodes(decodes, tracer) -> list:
    """The decode calls that started and ended inside the traced window:
    their device work lies inside it."""
    return [d for d in decodes
            if d[0] >= tracer.opened and d[1] <= tracer.closed]


def reference_gaps(fam, cfg, params, samples, control: bool = False):
    """The widest gap by which a served token's logit lies below the best of
    the family's float32 reference (matrix products at ``highest``), over
    the sampled requests; with ``control`` also the widest gap of the
    tokens that the same reference computed in bfloat16 puts first at
    those positions. Returns (gap, control gap or None, served tokens
    compared)."""
    import jax.numpy as jnp

    kw = fam.kwargs(cfg)
    s_len = cfg["service"]["max_len"]
    worst = {"program": 0.0, "control": 0.0}
    n_tok = 0
    for prompt, generated in samples:
        seq = list(prompt) + list(generated[:-1])
        toks = np.zeros(s_len, np.int32)
        toks[:len(seq)] = seq
        toks = jnp.asarray(toks)
        lo, n = len(prompt) - 1, len(generated)
        ref = fam.logits(params, toks, **kw)
        chosen = {"program": np.zeros(s_len, np.int32)}
        chosen["program"][lo:lo + n] = generated
        if control:
            low = fam.logits(params, toks, **kw, dtype=jnp.bfloat16)
            chosen["control"] = np.asarray(jnp.argmax(low, axis=1), np.int32)
        for side, pick in chosen.items():
            gaps = np.asarray(fam.token_gaps(ref, jnp.asarray(pick)))
            worst[side] = max(worst[side], float(gaps[lo:lo + n].max()))
        n_tok += n
    return worst["program"], worst["control"] if control else None, n_tok


def pick_samples(reqs, k: int, seed: int) -> list:
    """``k`` requests that were served tokens, drawn from the seed, and the
    longest of them."""
    served = [i for i, r in enumerate(reqs) if r.generated]
    if not served:
        return []
    longest = max(served, key=lambda i: len(reqs[i].prompt)
                  + len(reqs[i].generated))
    rest = [i for i in served if i != longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(rest, size=min(k, len(rest)), replace=False) \
        if rest else []
    return [longest] + [int(i) for i in pick]


def serve_once(ctx, cfg, params, mix, seconds, spans, tracer=None,
               alter=None, compiles=None, stop_at_close=True):
    """One serve of ``mix`` [(due_s, prompt, max_new)], stopped at the
    window's close unless ``stop_at_close`` is false (then it runs until
    every request is done). Returns (service, result or None, requests,
    probe, due times, window open, window close). Matrix products run at
    the configuration's ``matmul_precision``: its float32 is float32 (on a
    TPU, float32 products default to one bfloat16 pass)."""
    import jax

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        return _serve(ctx, cfg, params, mix, seconds, spans, tracer, alter,
                      compiles, stop_at_close)


def _serve(ctx, cfg, params, mix, seconds, spans, tracer, alter, compiles,
           stop_at_close):
    from repro.serving import SCHEDULERS
    from repro.serving.scheduler import ServeRequest

    tr = ctx.cell.traffic
    clock = DueClock([m[0] for m in mix])
    svc = build_service(cfg, params, len(mix), clock)
    n_prog = warm_entries(svc, tr["chunk"], cfg["service"]["max_batch"])
    harness.log(f"{n_prog} entry points warmed")
    fam = family.load(ctx.cell)
    probe = ServeProbe(svc, spans, partial(fam.token_flops, cfg["model"]),
                       tracer, alter, compiles)
    reqs = [ServeRequest(i, prompt, new, arrived_iter=i)
            for i, (_, prompt, new) in enumerate(mix)]
    sched = SCHEDULERS["chunked_prefill"](chunk=tr["chunk"])
    clock.t0 = time.perf_counter()
    t_open = clock.t0 + float(tr["warmup_s"])
    t_close = t_open + float(seconds)
    probe.trace_from, probe.window_to = t_open, t_close
    probe.trace_to = t_open + min(float(seconds), float(tr["trace_seconds"]))
    if stop_at_close:
        clock.close_at = t_close
    try:
        res = svc.serve_sync(reqs, sched, stream_name=ctx.cell.name)
    except WindowClosed:
        res = None
    probe.finish()
    due_abs = {i: clock.t0 + m[0] for i, m in enumerate(mix)}
    return svc, res, reqs, probe, due_abs, t_open, t_close


def run(ctx: harness.RunContext, alter=None, control: bool = False
        ) -> harness.RunOutput:
    """One run of a serving cell. ``alter`` plants a fault in the served
    tokens (tests); ``control`` also reads the gaps of the tokens the
    reference computed in bfloat16 puts first (``record["control"]``)."""
    import jax

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    fam = family.load(ctx.cell)
    params = fam.make_params(cfg["model"], harness.seed_child(ctx.seed, 1))
    jax.block_until_ready(params)
    mix = traffic.serve_requests(tr, harness.seed_child(ctx.seed, 2),
                                 ctx.seconds, cfg["model"]["vocab"])
    spans = harness.Spans(ctx.traced)
    compiles = harness.CompileCounter()
    tracer = harness.Tracer(ctx.cell.name) if ctx.traced else None
    svc, _, reqs, probe, _, t_open, t_close = serve_once(
        ctx, cfg, params, mix, ctx.seconds, spans, tracer, alter, compiles)
    mem = harness.memory_peak_bytes(harness.local_devices())
    traced = traced_decodes(probe.decodes, tracer) \
        if tracer is not None and tracer.closed is not None else None
    if traced is not None:        # after the peak: it loads programs
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            op_names = decode_op_names(
                svc, buckets(cfg["service"]["max_batch"]))
    alloc = svc.kv.allocator
    counters = {"blocks_peak_used": alloc.peak_used,
                "blocks_capacity": alloc.capacity}
    probe.detach()
    del svc                  # free the KV pools (the service sits in cycles)
    gc.collect()
    summary = tracer.reduce() if tracer else None

    # a traced run reads its per-layer numbers over the traced part alone:
    # the profiler's stop stalls the service right after it
    t_end = probe.trace_to if ctx.traced else t_close
    prefills = [p for p in probe.prefills if t_open <= p[1] < t_end]
    decodes = [d for d in probe.decodes if t_open <= d[1] < t_end]
    out_tok = sum(1 for p in prefills if p[4]) + sum(d[2] for d in decodes)
    flops = sum(fam.span_flops(cfg["model"], p[3], p[2])
                for p in prefills) + sum(d[3] for d in decodes)
    calls = sorted(prefills + decodes)
    stall = max((b[0] - a[1] for a, b in zip(calls, calls[1:])), default=0.0)
    notes = [f"window: {len(prefills)} prefill chunks, {len(decodes)} decode "
             f"steps, {out_tok} output tokens; longest decode call "
             f"{max((d[1] - d[0] for d in decodes), default=0.0)!r} s, "
             f"longest time between calls {stall!r} s; programs lowered by "
             f"its open and close {probe.lowered}; persistent cache "
             f"{compiles.cache}; KV blocks {counters}"]

    sample = pick_samples(reqs, tr["check_requests"],
                          harness.seed_child(ctx.seed, 3))
    picked = [(reqs[i].prompt, reqs[i].generated) for i in sample]
    gap, gap_control, n_tok = reference_gaps(fam, cfg, params, picked,
                                             control)
    if not sample:
        gap = np.inf
    notes.append(f"reference over {len(sample)} requests, {n_tok} served "
                 "tokens")
    # every prefill chunk of the serve up to the window's end: a window may
    # hold none, since a backlog admits only as requests finish
    record = {"window_s": t_end - t_open,
              "prefills": [p for p in probe.prefills if p[1] < t_end],
              "decodes": decodes, "counters": counters, "flops": flops,
              "peaks": work.peaks(harness.device_kind()),
              "spans": spans.items, "model": cfg["model"],
              "kv_itemsize": np.dtype(cfg["dtype"]).itemsize}
    if traced is not None:
        record.update(traced_decodes=traced, op_names=op_names)
        notes.append(f"traced: {len(traced)} decode steps, "
                     f"{sum(d[4] for d in traced)} positions attended")
    if control:
        record["control"] = gap_control
    return harness.RunOutput(
        t_open=t_open,
        end_to_end={"output_tokens_per_s": out_tok / (t_end - t_open)},
        record=record,
        checks=[("served_logit_gap", gap,
                 tr["limits"]["served_logit_gap"])],
        attempted=sum(1 for r in reqs if r.generated), failed=0,
        memory_peak_bytes=mem, trace=summary, notes=notes)
