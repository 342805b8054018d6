#!/usr/bin/env python3
"""Smoke run of both halves of the main path on a TPU.

    python chip_smoke.py             # one chip: search + serving phases
    python chip_smoke.py --chips 4   # four chips: sharded search only

One chip:

* **search** — ``compass.search_mapping`` on the paper's ``gpt3-7b`` with
  all 32 blocks under a seeded ShareGPT mixed prefill+decode stream, once
  through the compiled ``fused`` Pallas kernel and once through ``dense``.
  Both must give bitwise-identical GA histories, scores and mappings; the
  best mapping's timing matrix must match the numpy oracle to 1e-5; and
  the backend counters must name ``fused`` with no fallback recorded.
* **serving** — the published-width ``qwen1.5-0.5b`` (seeded random
  weights) serves a handful of seeded requests through
  ``AsyncLLMService`` over the paged KV pool; every request must finish
  and its tokens must equal ``ServingEngine``'s on the same requests and
  scheduler.

``--chips 4`` runs the search with its population sharded over four
devices against the same search on one device, and requires bitwise
equality. Nothing else runs then.

The script refuses to run without a TPU: there is no CPU fallback. Lines
before the last are smoke numbers (compile and wall seconds, shapes,
counts), not metrics. The last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEARCH_ARCH = "gpt3-7b"
SEARCH_BLOCKS = 32
SERVE_ARCH = "qwen1.5-0.5b"
GA = {"population": 32, "generations": 4, "seed": 0}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


# --------------------------------------------------------------------------
# Search
# --------------------------------------------------------------------------


def search_setup(spec=None, n_blocks: int = SEARCH_BLOCKS):
    """The smoke's search scenario: ``spec`` (default: the paper's
    gpt3-7b) under a seeded ShareGPT stream of cold prefills arriving into
    a warm decode pool, rolled out under chunked prefill, on a 16-chiplet
    heterogeneous WS/OS package."""
    from repro.configs import all_archs
    from repro.core import RequestStream, Scenario
    from repro.core.hardware import make_hardware
    from repro.core.traces import SHAREGPT

    spec = spec or all_archs()[SEARCH_ARCH].llm_spec()
    stream = RequestStream("sharegpt", trace=SHAREGPT, rate=2.0,
                           n_requests=16, warm_fraction=0.75,
                           max_new_tokens_cap=4, seed=0)
    sc = Scenario("chip-smoke", spec, target_tops=512, stream=stream,
                  scheduler="chunked_prefill", n_blocks=n_blocks,
                  max_stream_iters=6)
    hw = make_hardware(512, "L", tensor_parallel=8, micro_batch_prefill=2,
                       micro_batch_decode=2)
    hw = hw.replace(layout=tuple(["WS", "OS"] * (hw.n_chiplets // 2)))
    ro = sc.rollout()
    return sc, hw, ro


def run_search(setup, backend: str, devices: int, ga_config):
    from repro.core.compass import search_mapping

    sc, hw, ro = setup
    mbs = [sc.micro_batch(hw, b) for b in ro.batches]
    t0 = time.perf_counter()
    out = search_mapping(sc.spec, ro.batches, hw, mbs, ga_config,
                         objective="edp", n_blocks=sc.n_blocks, use_jax=True,
                         timing_backend=backend, devices=devices)
    return out, time.perf_counter() - t0


def check_same_search(a, b, what: str) -> None:
    """Bitwise identity of two searches: score, per-run GA histories and
    best scores, adopted mappings, per-batch latencies."""
    import numpy as np

    check(a.score == b.score, f"{what}: score {a.score!r} != {b.score!r}")
    check([r.history for r in a.ga_results]
          == [r.history for r in b.ga_results], f"{what}: GA history differs")
    check([r.best_score for r in a.ga_results]
          == [r.best_score for r in b.ga_results],
          f"{what}: GA best scores differ")
    check(a.encodings.keys() == b.encodings.keys(),
          f"{what}: structure groups differ")
    for key, enc in a.encodings.items():
        other = b.encodings[key]
        check(np.array_equal(enc.layer_to_chip, other.layer_to_chip)
              and np.array_equal(enc.segmentation, other.segmentation),
              f"{what}: best mapping of group {key} differs")
    check(np.array_equal(a.batch_latencies, b.batch_latencies),
          f"{what}: per-batch latencies differ")


def check_against_oracle(setup, out, backend: str, rtol: float = 1e-5):
    """The best mapping of every structure group, priced by the device
    evaluator's full timing matrix, against the float64 numpy oracle.
    Returns the largest relative error seen."""
    import numpy as np

    from repro.core.encoding import ScheduledOrderCache, as_stacked
    from repro.core.evaluator import evaluate
    from repro.core.jax_evaluator import GroupPopulationEvaluator
    from repro.core.timing import get_graph_and_tables

    sc, hw, ro = setup
    pairs = [get_graph_and_tables(sc.spec, b, hw, sc.micro_batch(hw, b),
                                  sc.n_blocks) for b in ro.batches]
    worst = 0.0
    for key, enc in out.encodings.items():
        group = [(g, t) for g, t in pairs if (g.rows, g.n_cols) == key]
        ge = GroupPopulationEvaluator([g for g, _ in group],
                                      [t for _, t in group], hw,
                                      backend=backend, devices=1)
        tm = ge.timing_matrix([enc])                   # (B, 1, T)
        g0 = group[0][0]
        order = ScheduledOrderCache(g0.rows, g0.n_cols).orders(
            as_stacked([enc]).segmentation)[0]          # (T, 2)
        for bi, (g, t) in enumerate(group):
            ref = evaluate(g, enc, hw, t).op_end_s[order[:, 0], order[:, 1]]
            got = tm.op_end_s[bi, 0]
            check(np.all(np.isfinite(got)), f"group {key}: non-finite ends")
            err = float(np.max(np.abs(got - ref) / np.abs(ref)))
            worst = max(worst, err)
            check(err <= rtol, f"group {key} batch {bi}: timing matrix is "
                  f"{err:.3e} from the numpy oracle (limit {rtol})")
    return worst


def search_phase(ga_config=None, setup=None) -> None:
    from repro.core import cache_stats
    from repro.core.ga import GAConfig
    from repro.core.timing import clear_timing_backend_stats

    ga_config = ga_config or GAConfig(**GA)
    setup = setup or search_setup()
    sc, hw, ro = setup
    clear_timing_backend_stats()
    fused, t_fused = run_search(setup, "fused", 1, ga_config)
    dense, t_dense = run_search(setup, "dense", 1, ga_config)
    keys = sorted(fused.encodings)
    log(f"search {sc.spec.name} n_blocks={sc.n_blocks} batches="
        f"{len(ro.batches)} groups(rows, M)={keys} T={[r * m for r, m in keys]}"
        f" chiplets={hw.n_chiplets} population={ga_config.population} "
        f"generations={ga_config.generations}")
    log(f"search wall incl. compile: fused {t_fused:.2f}s, dense "
        f"{t_dense:.2f}s; GA evaluations {fused.ga_evaluations}")
    check_same_search(fused, dense, "fused vs dense")
    stats = cache_stats()["timing_backend"]
    check(not stats["fallbacks"], f"backend fallbacks fired: {stats}")
    check(stats["dispatches"].get("fused", 0) > 0
          and stats["dispatches"].get("dense", 0) > 0,
          f"dispatch counts do not name fused and dense: {stats}")
    worst = check_against_oracle(setup, fused, "fused")
    log(f"search fused == dense bitwise; best timing matrix within "
        f"{worst:.3e} of the numpy oracle; backend counters {stats}")


def sharded_phase(n_devices: int, ga_config=None, setup=None) -> None:
    from repro.core.ga import GAConfig

    ga_config = ga_config or GAConfig(**GA)
    setup = setup or search_setup()
    one, t_one = run_search(setup, "fused", 1, ga_config)
    many, t_many = run_search(setup, "fused", n_devices, ga_config)
    log(f"sharded search wall incl. compile: 1 device {t_one:.2f}s, "
        f"{n_devices} devices {t_many:.2f}s")
    check_same_search(one, many, f"{n_devices} devices vs 1")
    log(f"sharded search on {n_devices} devices == 1 device bitwise")


# --------------------------------------------------------------------------
# Serving
# --------------------------------------------------------------------------


def serve_stream():
    """Six seeded requests with staggered arrivals; prompt lengths are
    multiples of 8 so chunked prefill (chunk 16) compiles two buckets."""
    from repro.core.streams import RequestStream, StreamRequest

    return RequestStream.from_requests([
        StreamRequest(32, 8, 0), StreamRequest(16, 6, 0),
        StreamRequest(48, 8, 1), StreamRequest(24, 5, 2),
        StreamRequest(40, 7, 4), StreamRequest(16, 8, 6),
    ], name="chip-smoke")


def serving_phase(cfg=None, max_batch: int = 4, max_len: int = 128) -> None:
    import jax

    from repro.configs import all_archs
    from repro.models import init_model
    from repro.serving import (SCHEDULERS, AsyncLLMService, ServiceConfig,
                               ServingEngine)
    from repro.serving.service import service_requests

    cfg = cfg or all_archs()[SERVE_ARCH].model
    t0 = time.perf_counter()
    params = init_model(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serve {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"vocab={cfg.vocab} params={n_params} init {time.perf_counter() - t0:.2f}s")

    stream = serve_stream()
    sched = SCHEDULERS["chunked_prefill"](chunk=16)
    svc = AsyncLLMService(params, cfg, ServiceConfig(
        max_batch=max_batch, max_len=max_len, block_len=16))
    t0 = time.perf_counter()
    res = svc.serve_sync(service_requests(stream, cfg.vocab, seed=0), sched,
                         stream_name=stream.name)
    t_svc = time.perf_counter() - t0
    check(not res.truncated and not res.unfinished,
          f"service left {len(res.unfinished)} requests unfinished")
    check(len(res.finished) == stream.n_requests,
          f"service finished {len(res.finished)} of {stream.n_requests}")

    eng = ServingEngine(params, cfg, max_batch=max_batch, max_len=max_len)
    t0 = time.perf_counter()
    fin, _ = eng.run(service_requests(stream, cfg.vocab, seed=0),
                     SCHEDULERS["chunked_prefill"](chunk=16))
    t_eng = time.perf_counter() - t0
    served = {r.rid: r.generated for r in res.finished}
    ref = {r.rid: r.generated for r in fin}
    n_tok = sum(len(v) for v in served.values())
    log(f"serve {len(served)} requests, {n_tok} tokens, "
        f"{len(res.stats)} iterations; wall incl. compile: service "
        f"{t_svc:.2f}s, engine {t_eng:.2f}s; blocks peak "
        f"{res.counters['blocks_peak_used']}/{res.counters['blocks_capacity']}")
    for rid in sorted(ref):
        if served.get(rid) != ref[rid]:
            log(f"request {rid}: service {served.get(rid)} engine {ref[rid]}")
    check(served == ref, "service tokens differ from ServingEngine's")
    log("serve tokens == ServingEngine tokens")


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded search, 4 devices vs 1")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r} "
              "devices", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "devices", file=sys.stderr)
        return 1

    import repro
    from repro.compile_cache import enable_compile_cache

    where = [Path(p).resolve() for p in repro.__path__]
    check(where == [ROOT / "src" / "repro"],
          f"imported repro from {where}, not from this checkout")
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    if args.chips == 1:
        search_phase()
        serving_phase()
    else:
        sharded_phase(args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
