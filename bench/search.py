"""Driver of the mapping-search cells.

Set-up builds the cell's scenario (model, package, request stream rolled
out by the program's scheduler) and runs one one-generation search so that
every group program is compiled and every cost table built. The window
then runs ``compass.search_mapping`` back to back, as a user calls it (no
timing backend named, no knob set), each search with its own GA seed
derived from the run's seed, until ``--seconds`` have passed; the search
under way then is stopped at its next evaluator call.

The harness's spans sit around ``GroupPopulationEvaluator.
evaluate_population``, the one call per generation of a structure group.
From each call in the window a reservoir drawn from the seed keeps one
individual's mapping and the prices the device returned for every batch
of the group; after the window the plain float64 reference
(``bench/reference/mapping.py``) prices the same mappings of the same
batches, and the widest relative gap of latency or energy is compared with
its limit.
"""
from __future__ import annotations

import hashlib
import time

import numpy as np

from bench import harness, work
from bench.reference import mapping as ref


class WindowClosed(Exception):
    """Raised at the first evaluator call after the window has closed."""


def build(cell):
    """(spec, hw, scenario, rollout, micro_batches) of a search cell."""
    from repro.core import RequestStream, Scenario
    from repro.core.hardware import HardwareConfig
    from repro.core.streams import StreamRequest
    from repro.core.workload import LLMSpec
    from repro.serving.scheduler import SCHEDULERS

    cfg, tr = cell.config, cell.traffic
    spec = LLMSpec(name=cfg["name"], **cfg["model"])
    pk = cfg["package"]
    hw = HardwareConfig(
        spec_name=pk["chiplet"], grid=tuple(pk["grid"]),
        layout=tuple(pk["layout"]), nop_bw_gbps=pk["nop_bw_gbps"],
        dram_bw_gbps=pk["dram_bw_gbps"],
        micro_batch_prefill=pk["micro_batch_prefill"],
        micro_batch_decode=pk["micro_batch_decode"],
        tensor_parallel=pk["tensor_parallel"])
    if hw.spec.macs != pk["macs"] or hw.spec.glb_bytes != pk["glb_bytes"]:
        raise harness.Refused(f"chiplet {pk['chiplet']!r} of the program is "
                              f"{hw.spec}, the configuration states "
                              f"{pk['macs']} MACs / {pk['glb_bytes']} B")
    from bench import traffic

    stream = RequestStream.from_requests(
        [StreamRequest(*r) for r in traffic.search_stream(tr["stream"])],
        name=cell.workload["traffic"])
    sched = SCHEDULERS[tr["scheduler"]["name"]](
        **{k: v for k, v in tr["scheduler"].items() if k != "name"})
    sc = Scenario(cell.name, spec, target_tops=pk["target_tops"],
                  stream=stream, scheduler=sched, n_blocks=tr["n_blocks"],
                  max_stream_iters=tr["rollout_iters"])
    ro = sc.rollout()
    mbs = [sc.micro_batch(hw, b) for b in ro.batches]
    return spec, hw, sc, ro, mbs


def ref_package(cfg) -> ref.Package:
    pk = cfg["package"]
    return ref.Package(pk["macs"], pk["glb_bytes"], tuple(pk["grid"]),
                       tuple(pk["layout"]), pk["nop_bw_gbps"],
                       pk["dram_bw_gbps"], pk["tensor_parallel"])


def ref_batches(ro):
    return [[(r.kind, r.q_len, r.kv_len) for r in b] for b in ro.batches]


def ref_micro_batch(cfg, batch) -> int:
    pk = cfg["package"]
    return pk["micro_batch_decode"] if any(k == "decode" for k, _, _ in batch) \
        else pk["micro_batch_prefill"]


def ref_groups(cfg, batches, n_blocks) -> dict:
    """{(rows, n_cols): [batch index]} as the reference builds the graphs."""
    groups: dict = {}
    for i, b in enumerate(batches):
        cols, ops, _ = ref.build_graph(cfg["model"], b,
                                       ref_micro_batch(cfg, b),
                                       cfg["package"]["tensor_parallel"],
                                       n_blocks)
        groups.setdefault((len(ops), len(cols)), []).append(i)
    return groups


class EvalProbe:
    """Wraps ``GroupPopulationEvaluator.evaluate_population``: host spans,
    the window's close, and a seeded reservoir of (group, mapping, prices)
    samples for the reference comparison."""

    def __init__(self, spans, n_samples: int, seed: int, alter=None):
        self.spans = spans
        self.rng = np.random.default_rng(seed)
        self.n_samples = n_samples
        self.alter = alter               # tests plant faults here
        self.calls: list = []            # (t0, t1, B, P, rows, M)
        self.samples: list = []
        self.seen = 0
        self.first: set = set()          # shapes seen in set-up
        self.open = None
        self.close = None

    def install(self):
        from repro.core import jax_evaluator

        cls = jax_evaluator.GroupPopulationEvaluator
        self._cls, self._orig = cls, cls.evaluate_population
        probe = self

        def evaluate_population(ev, population):
            return probe.call(ev, population)

        cls.evaluate_population = evaluate_population
        return self

    def uninstall(self):
        self._cls.evaluate_population = self._orig

    def call(self, ev, population):
        from repro.core.encoding import as_stacked

        if self.close is not None and time.perf_counter() >= self.close:
            raise WindowClosed
        with self.spans.span("bench.eval"):
            t0 = time.perf_counter()
            lat, en = self._orig(ev, population)
            t1 = time.perf_counter()
        if self.open is None:
            shape = (lat.shape, ev.graphs[0].rows, ev.graphs[0].n_cols)
            if shape not in self.first:
                self.first.add(shape)
                harness.log(f"first evaluator call (B, P) {lat.shape} "
                            f"T {shape[1] * shape[2]}: {t1 - t0:.2f}s")
        if self.alter is not None:
            lat, en = self.alter(lat, en)
        if self.open is None or t0 < self.open:
            return lat, en
        pop = as_stacked(population)
        rows, m = pop.layer_to_chip.shape[1:]
        self.calls.append((t0, t1, lat.shape[0], lat.shape[1], rows, m))
        # reservoir sampling over the window's calls
        self.seen += 1
        slot = len(self.samples) if len(self.samples) < self.n_samples \
            else int(self.rng.integers(self.seen))
        if slot < self.n_samples:
            j = int(self.rng.integers(lat.shape[1]))
            s = ((rows, m), pop.segmentation[j].copy(),
                 pop.layer_to_chip[j].copy(), lat[:, j].copy(),
                 en[:, j].copy())
            if slot == len(self.samples):
                self.samples.append(s)
            else:
                self.samples[slot] = s
        return lat, en


def check_samples(cfg, ro, n_blocks, samples, rnd=ref.identity) -> float:
    """Widest relative gap of latency or energy between the device's prices
    of the sampled mappings and the reference's (computed with ``rnd``)."""
    batches = ref_batches(ro)
    groups = ref_groups(cfg, batches, n_blocks)
    pkg = ref_package(cfg)
    worst = 0.0
    for key, seg, l2c, lat, en in samples:
        idxs = groups[key]
        if len(idxs) != len(lat):
            return float("inf")
        for bi, i in enumerate(idxs):
            b = batches[i]
            r_lat, r_en = ref.evaluate(cfg["model"], b, ref_micro_batch(cfg, b),
                                       pkg, n_blocks, seg, l2c, rnd=rnd)
            for got, want in ((lat[bi], r_lat), (en[bi], r_en)):
                gap = abs(float(got) - want) / abs(want)
                worst = max(worst, gap if np.isfinite(gap) else np.inf)
    return worst


def _digest(out) -> str:
    h = hashlib.sha256()
    for r in out.ga_results:
        h.update(np.asarray(r.history, dtype=np.float64).tobytes())
    return h.hexdigest()[:16]


def bf16(x) -> float:
    import ml_dtypes

    return float(ml_dtypes.bfloat16(x))


def run(ctx: harness.RunContext, alter=None, control: bool = False
        ) -> harness.RunOutput:
    """One run of a search cell. ``alter`` plants a fault in what the
    evaluator returns (tests); ``control`` also prices the samples with the
    reference carried out in bfloat16 (``record["control"]``)."""
    from repro.core import cache_stats, jax_evaluator
    from repro.core.compass import search_mapping
    from repro.core.ga import GAConfig
    from repro.core.timing import clear_timing_backend_stats

    cell, tr = ctx.cell, ctx.cell.traffic
    spec, hw, sc, ro, mbs = build(cell)
    ga = dict(cell.config["ga"])
    notes = []
    spans = harness.Spans(ctx.traced)
    probe = EvalProbe(spans, tr["check_samples"],
                      harness.seed_child(ctx.seed, 1), alter).install()
    compiles = harness.CompileCounter()
    tracer = harness.Tracer(cell.name) if ctx.traced else None

    def search(k: int, generations: int):
        cfg = GAConfig(**dict(ga, generations=generations),
                       seed=harness.seed_child(ctx.seed, 2, k))
        return search_mapping(spec, ro.batches, hw, mbs, cfg,
                              objective=tr["objective"],
                              n_blocks=tr["n_blocks"])

    harness.log(f"scenario built: {len(ro.batches)} batches")
    try:
        warm = search(0, 1)
        harness.log("warm-up search done")
        clear_timing_backend_stats()
        if tracer:
            tracer.start()
            tracer.open_window()
        n_compiled = compiles.count
        # a traced run measures the traced part of the window alone: the
        # profiler's stop would otherwise stall inside the window
        window = min(ctx.seconds, tr["trace_seconds"]) if tracer \
            else ctx.seconds
        probe.open = time.perf_counter()
        probe.close = probe.open + window
        searches = []
        k = 1
        while True:
            try:
                out = search(k, ga["generations"])
            except WindowClosed:
                break
            searches.append(out)
            notes.append(f"search {k}: score {out.score!r} evaluations "
                         f"{out.ga_evaluations} history {_digest(out)}")
            k += 1
        t_end = time.perf_counter()
        if tracer:
            tracer.stop()
    finally:
        probe.uninstall()
    window_compiles = compiles.count - n_compiled
    keys = sorted(warm.encodings)
    notes.insert(0, f"{spec.name} n_blocks={tr['n_blocks']} batches="
                 f"{len(ro.batches)} groups(rows, M)={keys} T="
                 f"{[r * m for r, m in keys]} P={ga['population']} "
                 f"generations={ga['generations']}")
    stats = cache_stats()
    notes.append(f"timing backend in the window: {stats['timing_backend']}")
    notes.append(f"jit cache sizes {jax_evaluator.jit_cache_sizes()}")
    notes.append(f"window: {len(probe.calls)} evaluator calls, "
                 f"{len(searches)} whole searches, {window_compiles} programs "
                 f"lowered; ran {t_end - probe.open:.3f}s for a "
                 f"{window}s window")

    close = probe.close
    done = [c for c in probe.calls if c[1] <= close]
    evals = sum(c[3] for c in done)
    mem = harness.memory_peak_bytes(harness.local_devices())
    summary = tracer.reduce() if tracer else None

    pk = work.peaks(harness.device_kind())
    w = max(hi - lo for lo, hi in ref.build_graph(
        cell.config["model"], ref_batches(ro)[0], 1,
        cell.config["package"]["tensor_parallel"], tr["n_blocks"])[0]
        if lo >= 0)
    least = 0.0
    for _, _, b, p, rows, m in done:
        ops, nbytes = work.search_eval_work(b, p, rows, m, w, hw.n_chiplets)
        least += work.least_time_s(ops, nbytes, pk)
    record = {"window_s": window, "calls": done, "least_time_s": least,
              "spans": spans.items, "open": probe.open, "close": close}

    harness.log(f"window closed after {len(probe.calls)} evaluator calls")
    rel = check_samples(cell.config, ro, tr["n_blocks"], probe.samples)
    harness.log("reference done")
    finite = all(np.isfinite(s[3]).all() and np.isfinite(s[4]).all()
                 for s in probe.samples)
    checks = [("eval_rel_err", rel, tr["limits"]["eval_rel_err"])]
    if control:
        record["control"] = check_samples(cell.config, ro, tr["n_blocks"],
                                          probe.samples, rnd=bf16)
    notes.append(f"reference priced {len(probe.samples)} sampled mappings")
    return harness.RunOutput(
        t_open=probe.open,
        end_to_end={"search_evals_per_s": evals / window},
        record=record, checks=checks, attempted=evals,
        failed=0 if finite else 1, memory_peak_bytes=mem, trace=summary,
        notes=notes)
