"""Paper Fig. 11 ablation: GA vs random mapping search, BO vs random
hardware sampling, SCAR-style greedy mapping — equal evaluation budgets."""
from .common import Timer, emit, ga_config


def run():
    from repro.core.baselines import scar_style_mapping
    from repro.core.bo import bo_search, random_hardware_search
    from repro.core.compass import Scenario, hardware_objective
    from repro.core.encoding import pipeline_parallel
    from repro.core.evaluator import CostTables, evaluate
    from repro.core.ga import ga_search, random_search
    from repro.core.hardware import make_hardware
    from repro.core.jax_evaluator import PopulationEvaluator
    from repro.configs import all_archs
    from repro.core.streams import mixed_serving_stream
    from repro.core.workload import build_execution_graph
    from repro.serving.scheduler import ChunkedPrefillScheduler

    spec = all_archs()["gpt3-7b"].llm_spec()
    # mixed chunked-prefill + decode batch on 16 heterogeneous chiplets:
    # the landscape where placement/pipelining actually matters
    sc = Scenario("gov-cp", spec, target_tops=512,
                  stream=mixed_serving_stream(4096, 600, 24, 2),
                  scheduler=ChunkedPrefillScheduler(chunk=2048), n_blocks=1)
    hw = make_hardware(512, "L", tensor_parallel=8, micro_batch_decode=8)
    hw = hw.replace(layout=tuple(["WS", "OS"] * (hw.n_chiplets // 2)))
    batch = sc.batches(hw)[0]
    g = build_execution_graph(spec, batch, hw.micro_batch_decode,
                              tp=8, n_blocks=1)
    tables = CostTables.build(g, hw)
    pe = PopulationEvaluator(g, tables, hw)

    def eval_fn(pop):
        lat, en = pe.evaluate_population(pop)
        return lat * en

    cfg = ga_config()
    cfg = cfg.__class__(population=max(cfg.population, 24),
                        generations=max(cfg.generations, 12))
    with Timer() as t:
        ga = ga_search(eval_fn, g.rows, g.n_cols, hw.n_chiplets, cfg)
        rnd = random_search(eval_fn, g.rows, g.n_cols, hw.n_chiplets,
                            budget=ga.evaluations, batch=cfg.population)
        scar = evaluate(g, scar_style_mapping(g, hw, tables), hw, tables)
        pp = evaluate(g, pipeline_parallel(g.rows, g.n_cols, hw.n_chiplets),
                      hw, tables)
    print(f"# mapping EDP: GA={ga.best_score:.4e} random={rnd.best_score:.4e} "
          f"SCAR-greedy={scar.edp:.4e} pipeline={pp.edp:.4e}")
    print(f"# GA vs random improvement: "
          f"{100*(1 - ga.best_score/rnd.best_score):.1f}%")
    emit("ablation_ga_vs_random", t.us,
         f"ga_wins={ga.best_score <= rnd.best_score}")

    # BO vs random hardware sampling (tiny budget)
    def hw_obj(point):
        from repro.core.ga import GAConfig
        s, _ = hardware_objective(sc, point, GAConfig(population=8,
                                                      generations=3))
        return s

    with Timer() as t:
        bo = bo_search(hw_obj, sc.target_tops, iters=5, init_points=4, seed=0)
        rh = random_hardware_search(hw_obj, sc.target_tops, iters=5,
                                    init_points=4, seed=1)
    print(f"# hardware search: BO={bo.best_score:.4e} random={rh.best_score:.4e}")
    emit("ablation_bo_vs_random", t.us, f"bo={bo.best_score:.3e}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
