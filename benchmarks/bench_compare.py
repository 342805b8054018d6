"""Paper Fig. 7: Compass vs Gemini-style vs MOHaM-style across scenarios
(trace x phase). Reduced budgets by default (COMPASS_FULL=1 for paper
scale). Reports latency / energy / monetary cost / total normalised to the
worst method per metric, plus the searched hardware (Table VI columns)."""
from .common import Timer, bo_budget, emit, ga_config


def scenarios():
    from repro.core.compass import Scenario
    from repro.core.traces import GOVREPORT, SHAREGPT
    from repro.configs import all_archs

    spec = all_archs()["gpt3-7b"].llm_spec()
    out = []
    for trace in (SHAREGPT, GOVREPORT):
        for phase, bs in (("prefill", 4), ("decode", 32)):
            out.append(Scenario(
                f"{trace.name}-{phase}-64T", spec, target_tops=64,
                phase=phase, trace=trace, batch_size=bs, n_batches=2,
                n_blocks=1))
    return out


def run():
    from repro.core.baselines import gemini_style_search, moham_style_search
    from repro.core.compass import co_explore
    from repro.core.ga import GAConfig

    iters, init = bo_budget()
    rows = []
    for sc in scenarios():
        with Timer() as t:
            comp = co_explore(sc, bo_iters=iters, bo_init=init,
                              ga_config=ga_config(), seed=0)
            gem = gemini_style_search(sc, sa_iters=60, grid_subsample=4)
            moh = moham_style_search(sc, generations=3, population=6,
                                     ga_config=GAConfig(population=8,
                                                        generations=3))
        res = {
            "compass": (comp.mapping.latency_s, comp.mapping.energy_j,
                        comp.mapping.mc_total, comp.hardware),
            "gemini": (gem.latency_s, gem.energy_j, gem.mc_total,
                       gem.hardware),
            "moham": (moh.latency_s, moh.energy_j, moh.mc_total,
                      moh.hardware),
        }
        lmax = max(v[0] for v in res.values())
        emax = max(v[1] for v in res.values())
        mmax = max(v[2] for v in res.values())
        tmax = max(v[0] * v[1] * v[2] for v in res.values())
        print(f"# scenario {sc.name}")
        for name, (l, e, m, hw) in res.items():
            ws = sum(1 for x in hw.layout if x == "WS")
            os_ = len(hw.layout) - ws
            print(f"#   {name:8s} L={l/lmax:.3f} E={e/emax:.3f} "
                  f"MC={m/mmax:.3f} total={(l*e*m)/tmax:.3f}  "
                  f"[hw: {hw.spec_name} nop={hw.nop_bw_gbps} "
                  f"dram={hw.dram_bw_gbps} mb={hw.micro_batch_prefill}/"
                  f"{hw.micro_batch_decode} tp={hw.tensor_parallel} "
                  f"WS={ws} OS={os_}]")
        rows.append((sc.name, res))
        emit(f"compare_{sc.name}", t.us,
             f"compass_total={res['compass'][0]*res['compass'][1]*res['compass'][2]:.3e}")
    # aggregate reductions vs each baseline (paper reports averages)
    for base in ("gemini", "moham"):
        dl = [1 - r["compass"][0] / r[base][0] for _, r in rows]
        de = [1 - r["compass"][1] / r[base][1] for _, r in rows]
        print(f"# avg reduction vs {base}: latency "
              f"{100*sum(dl)/len(dl):.1f}% energy {100*sum(de)/len(de):.1f}%")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
