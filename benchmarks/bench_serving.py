"""Paper §VI-F (Fig. 9/10, Table VII): DSE under the three serving
strategies on a GovReport-style long-context scenario, the
homogeneous-vs-heterogeneous comparison (Fig. 10b), and the multi-rate
goodput frontier — per-scheduler arrival-rate sweeps with the three
cross-group co-search modes (one_sweep / fixed_point / joint) as
comparable frontier lines. The rate grid is no longer fixed: each curve
is adaptively refined around its saturation knee
(``repro.core.frontier.refine_knee`` — boundary peaks extend the grid,
interior knees are bisection-bracketed within half the coarse spacing)
and every ``with_rate`` point prices the SAME request population
(rate-invariant streams), so the knees in BENCH_serving.json compare
goodput on identical requests. The mixed-scenario acceptance record also
pins the cross-mode warm start: a joint search seeded from the completed
fixed-point run must match-or-beat the cold joint.

Timing hygiene: every timed region here wraps a whole search
(``hardware_objective`` / ``search_mapping`` / ``co_explore``), and those
return host-side numpy scores — the ``np.asarray`` conversion inside the
evaluators is itself a device sync, so the ``Timer`` exits only after all
device work has drained (same guarantee ``common.sync`` gives the raw
population-pass benchmarks). The final record embeds
``repro.core.cache_stats()`` so cache behaviour across the run is
auditable next to the wall-clock numbers.

``--measured`` adds the sim-to-real section: the *real* async paged
service (``repro.serving.service``) runs the golden parity stream under
every scheduler, once on the deterministic iteration clock (where
measured-vs-planned TTFT/TPOT deltas must be exactly zero — the parity
contract) and once on a wall clock (where the deltas quantify how far
iteration-priced planning sits from event-time reality).
``--measured-only`` recomputes just that section and merges it into
``--out``."""
import argparse
import json
import time

from .common import (
    FULL,
    Timer,
    bo_budget,
    cosearch_modes,
    emit,
    fleet_budget,
    frontier_budget,
    ga_config,
    mixed_cosearch_scenario,
)


def goodput_frontier():
    """Goodput-vs-load frontier: for each scheduler and each co-search
    mode, sweep the Poisson arrival rate on a fixed hardware point under
    the goodput-under-SLO objective. The GA prices every candidate's
    rollout on true per-request timings, so rising load exposes the
    saturation knee (the rate of peak goodput) instead of a monotone
    latency proxy; fixed-point and joint lines are directly comparable to
    the one-sweep baseline because they share scenario, seed, GA budget
    AND (rate-invariance) the exact request population. Each curve's knee
    is adaptively refined: ties break to the highest rate, a peak on the
    grid boundary extends the grid (or is flagged ``knee_saturated``
    when the probe budget runs out), and interior knees are bracketed
    within ``rel_tol`` of the knee rate."""
    import numpy as np
    from repro.configs import all_archs
    from repro.core.bo import random_point
    from repro.core.compass import Scenario, hardware_objective
    from repro.core.frontier import refine_knee
    from repro.core.objectives import GoodputUnderSLO
    from repro.core.streams import RequestStream
    from repro.core.traces import SHAREGPT

    spec = all_archs()["llama3.2-3b"].llm_spec()
    point = random_point(np.random.default_rng(0), 512)
    fb = frontier_budget()
    schedulers = ("vllm", "orca", "chunked_prefill") if FULL \
        else ("orca", "chunked_prefill")
    n_req = fb["n_requests"]
    obj = GoodputUnderSLO(ttft_slo_s=0.5, tpot_slo_s=0.1)
    base = RequestStream("sharegpt-load", trace=SHAREGPT, rate=1.0,
                         n_requests=n_req, warm_fraction=0.25,
                         max_new_tokens_cap=8, seed=0)
    lines = []
    for sched in schedulers:
        for mode_name, cs in cosearch_modes().items():

            def evaluate(rate, sched=sched, mode_name=mode_name, cs=cs):
                sc = Scenario(f"load-{sched}-{mode_name}-{rate:g}", spec,
                              target_tops=512, stream=base.with_rate(rate),
                              scheduler=sched, objective=obj, n_blocks=2,
                              max_stream_iters=96, co_search=cs)
                with Timer() as t:
                    score, out = hardware_objective(sc, point, ga_config())
                goodput = -score        # requests/s meeting both SLOs
                print(f"# {sched:16s} {mode_name:11s} rate={rate:7.3f} "
                      f"goodput={goodput:9.3f} req/s rounds={out.rounds} "
                      f"conv={out.converged}")
                emit(f"frontier_{sched}_{mode_name}_{rate:g}", t.us,
                     f"goodput={goodput:.4f}")
                return goodput, {
                    "rounds": out.rounds,
                    "converged": out.converged,
                    "ga_evaluations": out.ga_evaluations,
                    "wall_s": round(t.us / 1e6, 2),
                }

            res = refine_knee(evaluate, fb["coarse_rates"],
                              rel_tol=fb["rel_tol"],
                              max_probes=fb["max_probes"],
                              extend_factor=fb["extend_factor"])
            curve = [{"rate": p.rate,
                      "goodput_req_per_s": round(p.goodput, 4),
                      **p.meta} for p in res.points]
            lo, hi = res.bracket
            lines.append({
                "scheduler": sched,
                "mode": mode_name,
                "curve": curve,
                "knee_rate": res.knee_rate,
                "peak_goodput_req_per_s": round(res.peak_goodput, 4),
                "knee_saturated": res.knee_saturated,
                "knee_bracket": [lo, hi],
                "knee_converged": res.converged,
                "refine_probes": res.probes,
            })
            emit(f"frontier_knee_{sched}_{mode_name}", 0,
                 f"knee={res.knee_rate:g} saturated={res.knee_saturated}")
    # per (scheduler, shared rate), the fixed point must dominate the one
    # sweep — adaptive refinement probes different rates per mode, so the
    # comparison runs on the rates both curves priced (the coarse grid at
    # minimum)
    by_key = {(ln["scheduler"], ln["mode"]): ln for ln in lines}

    def _by_rate(line):
        return {pt["rate"]: pt["goodput_req_per_s"] for pt in line["curve"]}

    dominated = True
    for sched in schedulers:
        fp = _by_rate(by_key[(sched, "fixed_point")])
        os_ = _by_rate(by_key[(sched, "one_sweep")])
        for rate in sorted(set(fp) & set(os_)):
            dominated &= fp[rate] >= os_[rate] - 1e-9
    emit("frontier_fixed_point_dominates_one_sweep", 0, f"ok={dominated}")
    return {
        "objective": obj.name,
        "coarse_rates": list(fb["coarse_rates"]),
        "rel_tol": fb["rel_tol"],
        "max_probes": fb["max_probes"],
        "n_requests": n_req,
        "lines": lines,
        "fixed_point_dominates_one_sweep": dominated,
    }


def fleet_frontier_record():
    """Fleet frontier: goodput-per-dollar vs offered load, replica count
    annotated per point (the ROADMAP's fleet output record).

    At each rate on a fixed grid the scale-out policy search compares the
    operator's options — keep the 1-replica fleet, add a replica (the
    router splits the stream deterministically, so both points price the
    SAME request population), swap the scheduler, or re-search the
    mapping warm-started from the completed search (PR 5's ``warm_from``
    carrier, threaded through the replica's compass pricer) — and the
    frontier records the winning option's goodput-per-dollar. Replicas
    price their rollouts with a full mapping search on a fixed hardware
    config; the dollar denominator is the searched point's own
    ``mc_total`` summed over replicas. ``sweep_knee`` (fixed grid, no
    refinement: each probe is several mapping searches) supplies the
    knee bookkeeping, so this record's knee conventions match the
    refined single-server frontier's."""
    import numpy as np
    from repro.configs import all_archs
    from repro.core.compass import search_mapping
    from repro.core.frontier import sweep_knee
    from repro.core.hardware import make_hardware
    from repro.core.objectives import GoodputUnderSLO
    from repro.core.streams import RequestStream, rollout
    from repro.core.traces import SHAREGPT
    from repro.core.workload import DECODE
    from repro.fleet import Fleet, PlannedReplica, compass_pricer, \
        plan_scale_out
    from repro.serving.scheduler import get_scheduler

    fb = fleet_budget()
    spec = all_archs()["llama3.2-3b"].llm_spec()
    hw = make_hardware(512, "L", tensor_parallel=8)
    hw = hw.replace(layout=tuple(["WS", "OS"] * (hw.n_chiplets // 2)))
    base = RequestStream("sharegpt-fleet", trace=SHAREGPT, rate=1.0,
                         n_requests=fb["n_requests"], warm_fraction=0.25,
                         max_new_tokens_cap=8, seed=0)

    # SLOs from a latency pre-search at the middle of the load grid, set
    # at the 60th percentile of its timings — binding but not zeroing
    # goodput at this hardware scale (mixed_cosearch_scenario's recipe)
    mid = sorted(fb["rates"])[len(fb["rates"]) // 2]
    pre_ro = rollout(base.with_rate(mid), get_scheduler("orca"),
                     max_slots=fb["max_slots"], max_iters=fb["max_iters"])
    pre_mbs = [hw.micro_batch_decode
               if any(r.kind == DECODE for r in b) else hw.micro_batch_prefill
               for b in pre_ro.batches]
    pre = search_mapping(spec, pre_ro.batches, hw, pre_mbs, ga_config(),
                         objective="latency", n_blocks=2)
    pre_tim = pre_ro.timings(pre.batch_latencies)
    obj = GoodputUnderSLO(
        ttft_slo_s=float(np.percentile(pre_tim.cold_ttft_s, 60)),
        tpot_slo_s=float(np.percentile(pre_tim.tpot_s, 60)))

    def replica(name="r0", warm_from=None):
        return PlannedReplica(
            pricer=compass_pricer(spec, hw, ga_config(), objective=obj,
                                  n_blocks=2, warm_from=warm_from),
            scheduler="orca", max_slots=fb["max_slots"],
            max_iters=fb["max_iters"], name=name)

    def re_search(rep, res):
        # warm-start the next mapping search from the keep-serve's
        # completed search (carried in the compass pricer's meta)
        return replica(name=f"{rep.name}'",
                       warm_from=res.meta.get("search_output"))

    points = []

    def evaluate(rate):
        with Timer() as t:
            dec = plan_scale_out(
                Fleet([replica()]), base, rate, objective=obj,
                schedulers=fb["schedulers"], re_search=re_search)
        best = dec.best
        print(f"# fleet rate={rate:7.3f} best={best.action:12s} "
              f"replicas={best.fleet.n_replicas} "
              f"goodput/$={best.score:9.4f} wall={t.us/1e6:.1f}s")
        emit(f"fleet_frontier_{rate:g}", t.us,
             f"best={best.action} gpd={best.score:.4f}")
        points.append({
            "rate": rate,
            "best_action": best.action,
            "n_replicas": best.fleet.n_replicas,
            "goodput_per_dollar": round(best.score, 6),
            "goodput_req_per_s": round(best.result.goodput(obj), 4),
            "mc_total": round(best.result.mc_total, 1),
            "loads": best.result.route.loads().tolist(),
            "options": [
                {"action": o.action,
                 "n_replicas": o.fleet.n_replicas,
                 "goodput_per_dollar":
                     None if o.score == float("-inf")
                     else round(o.score, 6),
                 "truncated": bool(o.result and o.result.truncated)}
                for o in dec.options],
            "wall_s": round(t.us / 1e6, 2),
        })
        return best.score, {}

    res = sweep_knee(evaluate, fb["rates"])
    emit("fleet_frontier_knee", 0,
         f"knee={res.knee_rate:g} saturated={res.knee_saturated}")
    return {
        "objective": f"goodput_per_dollar@ttft{obj.ttft_slo_s:.3g}s"
                     f"/tpot{obj.tpot_slo_s:.3g}s",
        "slo_percentile_of_latency_presearch": 60,
        "rates": list(fb["rates"]),
        "n_requests": fb["n_requests"],
        "max_slots_per_replica": fb["max_slots"],
        "points": points,
        "knee_rate": res.knee_rate,
        "peak_goodput_per_dollar": round(res.peak_goodput, 6),
        "knee_saturated": res.knee_saturated,
    }


def fixed_point_vs_one_sweep():
    """Acceptance record: on the mixed prefill+decode stream scenario
    (>= 2 structure groups, so the cross-group coupling is real) the
    fixed-point co-search must converge and reach goodput >= the one-sweep
    baseline, and a joint search warm-started from the completed
    fixed-point run (cross-mode warm start) must match-or-beat the cold
    joint."""
    from repro.core.compass import CoSearchConfig, search_mapping

    spec, hw, ro, mbs, obj = mixed_cosearch_scenario(
        n_blocks=2, max_stream_iters=96, ga_cfg=ga_config())
    rec = {"scenario": "sharegpt mixed prefill+decode (orca)",
           "objective": obj.name,
           "n_batches": len(ro.batches)}
    outs = {}
    # let the acceptance run iterate to the actual fixed point; then seed
    # a joint population from its adopted per-group elites
    modes = dict(cosearch_modes(max_rounds_fp=8))

    def run(mode_name, cs):
        with Timer() as t:
            out = search_mapping(spec, ro.batches, hw, mbs, ga_config(),
                                 objective=obj, n_blocks=2,
                                 stream_rollout=ro, co_search=cs)
        outs[mode_name] = out
        rec[mode_name] = {
            "goodput_req_per_s": round(-out.score, 4),
            "rounds": out.rounds,
            "converged": out.converged,
            "ga_evaluations": out.ga_evaluations,
            "n_groups": len(out.encodings),
            "wall_s": round(t.us / 1e6, 2),
        }
        print(f"# mix {mode_name:11s} goodput={-out.score:9.3f} req/s "
              f"rounds={out.rounds} conv={out.converged} "
              f"groups={len(out.encodings)}")
        emit(f"mix_cosearch_{mode_name}", t.us, f"goodput={-out.score:.4f}")

    for mode_name, cs in modes.items():
        run(mode_name, cs)
    run("joint_warm", CoSearchConfig(mode="joint",
                                     warm_from=outs["fixed_point"],
                                     warm_fraction=0.5))
    ratio = rec["fixed_point"]["goodput_req_per_s"] \
        / max(rec["one_sweep"]["goodput_req_per_s"], 1e-30)
    rec["fixed_point_over_one_sweep"] = round(ratio, 4)
    ok = rec["fixed_point"]["converged"] and ratio >= 1.0 - 1e-9
    rec["acceptance_converged_and_no_worse"] = ok
    emit("mix_cosearch_acceptance", 0, f"ok={ok}")
    warm_ratio = rec["joint_warm"]["goodput_req_per_s"] \
        / max(rec["joint"]["goodput_req_per_s"], 1e-30)
    rec["warm_joint_over_cold_joint"] = round(warm_ratio, 4)
    warm_ok = warm_ratio >= 1.0 - 1e-9
    rec["acceptance_warm_joint_no_worse_than_cold"] = warm_ok
    emit("mix_cosearch_warm_joint_acceptance", 0, f"ok={warm_ok}")
    return rec


def measured_service_record():
    """Measured-vs-planned on the real serving subsystem (small model,
    CPU-friendly). For each scheduler:

    * deterministic clock — the service's measured ``StreamRollout`` must
      equal the planner's bit for bit, so TTFT/TPOT deltas (both priced
      with the measured per-iteration seconds) are asserted ``== 0``;
    * wall clock — measured wall-event timings vs the planner's schedule
      priced with that run's measured per-iteration seconds: the residual
      is real queueing/transfer time the iteration abstraction hides.
    """
    import jax
    import numpy as np
    from repro.configs import all_archs
    from repro.core.streams import rollout
    from repro.models import init_model
    from repro.serving import (
        SCHEDULERS,
        AsyncLLMService,
        ServiceConfig,
        WallClock,
    )
    from repro.serving.service import golden_parity_stream, service_requests

    cfg = all_archs()["qwen1.5-0.5b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    stream = golden_parity_stream()
    svc_cfg = ServiceConfig(max_batch=3, max_len=64, block_len=16)

    def sched(name):
        return (SCHEDULERS[name](chunk=8) if name == "chunked_prefill"
                else SCHEDULERS[name]())

    def delta(a, b):
        d = np.abs(np.asarray(a) - np.asarray(b))
        d = d[np.isfinite(d)]
        return {"mean": round(float(d.mean()), 6),
                "max": round(float(d.max()), 6)} if d.size else None

    recs = {}
    for name in ("vllm", "orca", "chunked_prefill"):
        svc = AsyncLLMService(params, cfg, svc_cfg)
        with Timer() as t_det:
            res = svc.serve_sync(service_requests(stream, cfg.vocab),
                                 sched(name), stream_name=stream.name)
        ro = rollout(stream, sched(name), max_slots=svc_cfg.max_batch,
                     max_iters=10_000)
        parity = res.rollout.batches == ro.batches
        planned = ro.timings(res.iteration_seconds)
        measured = res.timings()
        det_ttft = delta(planned.ttft_s, measured.ttft_s)
        det_tpot = delta(planned.tpot_s, measured.tpot_s)
        assert parity and det_ttft["max"] == 0 and det_tpot["max"] == 0, \
            f"parity broken for {name}"

        wall_svc = AsyncLLMService(params, cfg, svc_cfg,
                                   clock=WallClock(period_s=0.01))
        with Timer() as t_wall:
            wres = wall_svc.serve_sync(service_requests(stream, cfg.vocab),
                                       sched(name), stream_name=stream.name)
        wall = wres.wall_timings()
        wall_planned = wres.timings()     # its own schedule, iteration-priced
        recs[name] = {
            "parity_bitwise": parity,
            "iterations": len(res.stats),
            "deterministic_delta_ttft_s": det_ttft,
            "deterministic_delta_tpot_s": det_tpot,
            "wall_iterations": len(wres.stats),
            "wall_delta_ttft_s": delta(wall.ttft_s, wall_planned.ttft_s),
            "wall_delta_tpot_s": delta(wall.tpot_s, wall_planned.tpot_s),
            "wall_makespan_s": round(float(wall.makespan_s), 4),
            "blocks_peak_used": res.counters["blocks_peak_used"],
            "transfer_pool_hit_rate": round(
                res.counters["transfer_pool_hits"]
                / max(res.counters["transfer_pool_hits"]
                      + res.counters["transfer_pool_misses"], 1), 3),
            "wall_s": round((t_det.us + t_wall.us) / 1e6, 2),
        }
        print(f"# measured {name:16s} parity={parity} "
              f"wall_dTTFT={recs[name]['wall_delta_ttft_s']['mean']}s "
              f"wall_dTPOT={recs[name]['wall_delta_tpot_s']['mean']}s")
        emit(f"measured_service_{name}", t_det.us + t_wall.us,
             f"parity={parity}")
    return {
        "stream": stream.name,
        "n_requests": stream.n_requests,
        "service": {"max_batch": svc_cfg.max_batch,
                    "max_len": svc_cfg.max_len,
                    "block_len": svc_cfg.block_len},
        "schedulers": recs,
    }


def _merge_section(out_path: str, key: str, section) -> dict:
    """Recompute one section and merge it into the existing record."""
    rec = {}
    try:
        with open(out_path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        pass
    rec[key] = section
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2)
            f.write("\n")
    return rec


def run(out_path: str = "BENCH_serving.json", measured: bool = False,
        measured_only: bool = False, fleet: bool = False,
        fleet_only: bool = False):
    if measured_only:
        return _merge_section(out_path, "measured_service",
                              measured_service_record())
    if fleet_only:
        return _merge_section(out_path, "fleet_frontier",
                              fleet_frontier_record())
    t0 = time.time()
    frontier = goodput_frontier()
    mix = fixed_point_vs_one_sweep()

    from repro.core.compass import Scenario, co_explore, hardware_objective
    from repro.core.streams import mixed_serving_stream
    from repro.configs import all_archs
    from repro.core.bo import HardwarePoint
    from repro.core.hardware import DATAFLOWS
    from repro.serving.scheduler import ChunkedPrefillScheduler

    spec = all_archs()["gpt3-7b"].llm_spec()
    # GovReport-512T scaled down: 1 prefill (long input) + warm decode pool,
    # rolled out under each real scheduler policy
    stream = mixed_serving_stream(prefill_len=4096, decode_ctx=600,
                                  decode_bs=32, n_decode_batches=3)
    iters, init = bo_budget()
    results = {}
    gov = {}
    for name, sched in [("vllm", "vllm"), ("orca", "orca"),
                        ("chunked_prefill",
                         ChunkedPrefillScheduler(chunk=2048))]:
        sc = Scenario(f"gov-{name}", spec, target_tops=512, stream=stream,
                      scheduler=sched, n_blocks=1)
        with Timer() as t:
            res = co_explore(sc, bo_iters=iters, bo_init=init,
                             ga_config=ga_config(), seed=0)
        hw = res.hardware
        ws = sum(1 for x in hw.layout if x == "WS")
        print(f"# {name:16s} L={res.mapping.latency_s*1e3:9.2f}ms "
              f"E={res.mapping.energy_j:8.3f}J MC=${res.mapping.mc_total:.1f} "
              f"[{hw.spec_name} dram={hw.dram_bw_gbps} nop={hw.nop_bw_gbps} "
              f"WS={ws} OS={hw.n_chiplets-ws}]")
        results[name] = res
        gov[name] = {"edp": res.mapping.edp,
                     "latency_ms": round(res.mapping.latency_s * 1e3, 3),
                     "mc_total": round(res.mapping.mc_total, 1)}
        emit(f"serving_{name}", t.us,
             f"edp={res.mapping.edp:.3e}")

    # Fig. 10b: homogenise the chunked-prefill winner
    best = results["chunked_prefill"]
    sc = Scenario("gov-cp-fixed", spec, target_tops=512, stream=stream,
                  scheduler=ChunkedPrefillScheduler(chunk=2048), n_blocks=1)
    edps = {}
    for tag, layout in [("hetero", best.point.layout),
                        ("all_WS", tuple([DATAFLOWS.index("WS")]
                                         * len(best.point.layout))),
                        ("all_OS", tuple([DATAFLOWS.index("OS")]
                                         * len(best.point.layout)))]:
        pt = HardwarePoint(best.point.spec_name, best.point.sys_idx, layout)
        score, out = hardware_objective(sc, pt, ga_config(), "edp")
        _ = score
        edps[tag] = out.edp
        print(f"# fig10b {tag:7s} EDP={out.edp:.4e}")
    for tag in ("all_WS", "all_OS"):
        print(f"# hetero EDP reduction vs {tag}: "
              f"{100*(1 - edps['hetero']/edps[tag]):.1f}%")
    emit("serving_homo_vs_hetero", 0,
         f"hetero<=minhomo: {edps['hetero'] <= min(edps['all_WS'], edps['all_OS']) * 1.05}")

    from repro.core import cache_stats

    rec = {
        "benchmark": "serving",
        "full": FULL,
        "wall_s": round(time.time() - t0, 1),
        "frontier": frontier,
        "fixed_point_vs_one_sweep": mix,
        "govreport_dse": gov,
        "fig10b_edp": edps,
        "cache_stats": cache_stats(),
    }
    if measured:
        rec["measured_service"] = measured_service_record()
    if fleet:
        rec["fleet_frontier"] = fleet_frontier_record()
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2)
            f.write("\n")
    return rec


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="output JSON path")
    ap.add_argument("--measured", action="store_true",
                    help="also run the real async service and record "
                         "measured-vs-planned TTFT/TPOT deltas")
    ap.add_argument("--measured-only", action="store_true",
                    help="recompute only the measured-service section and "
                         "merge it into --out")
    ap.add_argument("--fleet", action="store_true",
                    help="also run the fleet frontier (goodput-per-dollar "
                         "vs offered load, replica count annotated)")
    ap.add_argument("--fleet-only", action="store_true",
                    help="recompute only the fleet-frontier section and "
                         "merge it into --out")
    args = ap.parse_args()
    run(args.out, measured=args.measured, measured_only=args.measured_only,
        fleet=args.fleet, fleet_only=args.fleet_only)
