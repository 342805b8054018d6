"""Device-resident mapping search: batched/vectorised paths vs the
per-individual references (this PR's tentpole)."""
import numpy as np
import pytest

from repro.analysis import is_legal, verify_encoding
from repro.core import compass
from repro.core.encoding import (
    MappingEncoding,
    ScheduledOrderCache,
    StackedPopulation,
    random_encoding,
    scheduled_orders,
)
from repro.core.evaluator import CostTables, evaluate
from repro.core.ga import (
    GAConfig,
    crossover_population,
    ga_search,
    mutate,
    mutate_population,
    tournament_select,
)
from repro.core.hardware import make_hardware
from repro.core.workload import (
    LLMSpec,
    MoESpec,
    build_execution_graph,
    decode_request,
    prefill_request,
)

SPEC = LLMSpec("t", 256, 4, 4, 64, 1024, 1000, 8)
HW = make_hardware(256, "M", tensor_parallel=2)  # 8 chiplets


def _cases():
    return [
        (LLMSpec("dense", 256, 4, 4, 64, 1024, 1000, 8),
         [prefill_request(128), prefill_request(64), decode_request(300)], 2),
        (LLMSpec("moe", 256, 4, 2, 64, 1024, 1000, 8,
                 moe=MoESpec(8, 1, 2, 128)),
         [decode_request(100 + 37 * i) for i in range(4)], 2),
        (LLMSpec("mamba", 256, 0, 0, 64, 0, 1000, 8, attn_kind="none",
                 mixer="mamba", d_inner=512, ssm_state=16),
         [prefill_request(200), decode_request(500)], 1),
    ]


# ---------------------------------------------------------------------------
# CostTables vectorised build
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", range(3))
def test_cost_tables_build_matches_reference(case):
    spec, batch, mb = _cases()[case]
    hw = make_hardware(64, "M", layout=None, tensor_parallel=2)
    hw = hw.replace(layout=tuple(["WS", "OS"] * (hw.n_chiplets // 2)))
    g = build_execution_graph(spec, batch, mb, tp=2, n_blocks=2)
    ref = CostTables.build_reference(g, hw)
    new = CostTables.build(g, hw)
    for f in ref.__dataclass_fields__:
        np.testing.assert_allclose(
            getattr(ref, f), getattr(new, f), rtol=1e-9, atol=0,
            err_msg=f"CostTables.{f} diverges from the loop reference")


# ---------------------------------------------------------------------------
# scheduled_orders
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,m_cols", [(1, 1), (2, 3), (4, 10), (3, 7)])
def test_scheduled_orders_matches_per_individual(rows, m_cols):
    rng = np.random.default_rng(0)
    encs = [random_encoding(rng, rows, m_cols, 4, p_seg=0.4)
            for _ in range(16)]
    segs = np.stack([e.segmentation for e in encs])
    vec = scheduled_orders(segs, rows, m_cols)
    for i, e in enumerate(encs):
        np.testing.assert_array_equal(vec[i], e.scheduled_order())


def test_scheduled_order_cache_hits_on_unchanged_segmentation():
    rng = np.random.default_rng(1)
    encs = [random_encoding(rng, 3, 8, 4, p_seg=0.3) for _ in range(8)]
    segs = np.stack([e.segmentation for e in encs])
    cache = ScheduledOrderCache(3, 8)
    first = cache.orders(segs)
    assert cache.misses == 8
    again = cache.orders(segs)
    assert cache.misses == 8 and cache.hits == 8
    np.testing.assert_array_equal(first, again)
    for i, e in enumerate(encs):
        np.testing.assert_array_equal(first[i], e.scheduled_order())


# ---------------------------------------------------------------------------
# grouped population evaluator
# ---------------------------------------------------------------------------


def test_group_evaluator_matches_numpy_oracle():
    jax_eval = pytest.importorskip("repro.core.jax_evaluator")
    spec, _, _ = _cases()[0]
    hw = make_hardware(64, "M", layout=None, tensor_parallel=2)
    hw = hw.replace(layout=tuple(["WS", "OS"] * (hw.n_chiplets // 2)))
    batches = [
        [prefill_request(128), prefill_request(64), decode_request(300)],
        [prefill_request(30), prefill_request(31), decode_request(77)],
    ]
    graphs = [build_execution_graph(spec, b, 2, tp=2, n_blocks=2)
              for b in batches]
    tables = [CostTables.build(g, hw) for g in graphs]
    ge = jax_eval.GroupPopulationEvaluator(graphs, tables, hw)
    rng = np.random.default_rng(0)
    pop = [random_encoding(rng, graphs[0].rows, graphs[0].n_cols,
                           hw.n_chiplets) for _ in range(6)]
    lat, en = ge.evaluate_population(pop)
    assert lat.shape == (2, 6) and en.shape == (2, 6)
    for bi, (g, t) in enumerate(zip(graphs, tables)):
        for pi, enc in enumerate(pop):
            r = evaluate(g, enc, hw, t)
            assert lat[bi, pi] == pytest.approx(r.latency_s, rel=1e-4)
            assert en[bi, pi] == pytest.approx(r.energy_j, rel=1e-4)
    # stacked-population input is the same computation
    lat2, _ = ge.evaluate_population(StackedPopulation.from_encodings(pop))
    np.testing.assert_array_equal(lat, lat2)


def test_one_compile_per_shape_across_generations():
    from repro.core import jax_evaluator as je

    spec, batch, mb = _cases()[0]
    hw = make_hardware(64, "M", layout=None, tensor_parallel=2)
    hw = hw.replace(layout=tuple(["WS", "OS"] * (hw.n_chiplets // 2)))
    g = build_execution_graph(spec, batch, mb, tp=2, n_blocks=2)
    t = CostTables.build(g, hw)
    before = je.jit_cache_sizes()["grouped_population_pass"]
    rng = np.random.default_rng(0)
    # two evaluator instances with the same shapes (as across BO
    # iterations), several generations each: at most ONE new compile
    for trial in range(2):
        ge = je.GroupPopulationEvaluator([g, g], [t, t], hw)
        for gen in range(3):
            pop = [random_encoding(rng, g.rows, g.n_cols, hw.n_chiplets)
                   for _ in range(4)]
            ge.evaluate_population(pop)
    after = je.jit_cache_sizes()["grouped_population_pass"]
    assert after - before <= 1


# ---------------------------------------------------------------------------
# use_jax handling in compass
# ---------------------------------------------------------------------------


def _tiny_group():
    g = build_execution_graph(SPEC, [prefill_request(64 * (i + 1))
                                     for i in range(4)], 2, tp=2, n_blocks=1)
    t = CostTables.build(g, HW)
    return [g], [t]


def test_use_jax_true_raises_instead_of_degrading(monkeypatch):
    import repro.core.jax_evaluator as je

    def boom(*a, **k):
        raise RuntimeError("synthetic jax failure")

    monkeypatch.setattr(je, "GroupPopulationEvaluator", boom)
    graphs, tables = _tiny_group()
    with pytest.raises(RuntimeError, match="synthetic jax failure"):
        compass._make_population_eval(graphs, tables, HW, use_jax=True)


def test_use_jax_auto_warns_on_fallback(monkeypatch):
    """The numpy fallback under ``use_jax=None`` is gone: an evaluator
    failure propagates instead of being warned about and hidden, and
    ``use_jax=False`` stays the explicit (and correct) host path."""
    import repro.core.jax_evaluator as je

    def boom(*a, **k):
        raise RuntimeError("synthetic jax failure")

    monkeypatch.setattr(je, "GroupPopulationEvaluator", boom)
    graphs, tables = _tiny_group()
    with pytest.raises(RuntimeError, match="synthetic jax failure"):
        compass._make_population_eval(graphs, tables, HW, use_jax=None)
    fn = compass._make_population_eval(graphs, tables, HW, use_jax=False)
    rng = np.random.default_rng(0)
    pop = [random_encoding(rng, graphs[0].rows, graphs[0].n_cols,
                           HW.n_chiplets)]
    lat, en = fn(pop)
    r = evaluate(graphs[0], pop[0], HW, tables[0])
    assert lat[0, 0] == pytest.approx(r.latency_s)
    assert en[0, 0] == pytest.approx(r.energy_j)


# ---------------------------------------------------------------------------
# vectorised GA operators
# ---------------------------------------------------------------------------


def _random_stack(rng, p, rows, m_cols, n_chips, p_seg=0.3):
    return StackedPopulation.from_encodings(
        [random_encoding(rng, rows, m_cols, n_chips, p_seg=p_seg)
         for _ in range(p)])


def test_tournament_select_prefers_better_scores():
    rng = np.random.default_rng(0)
    scores = np.arange(32, dtype=float)
    idx = tournament_select(rng, scores, k=3, n=4000)
    assert idx.min() >= 0 and idx.max() < 32
    # winners are biased towards low scores; the best individual wins a
    # 3-tournament with prob 1 - (29/32)(28/31)(27/30) ~ 0.27
    assert (scores[idx] < 8).mean() > 0.45


def test_crossover_population_structure_and_validity():
    rng = np.random.default_rng(0)
    p, rows, m_cols, n_chips = 24, 3, 10, HW.n_chiplets
    a = _random_stack(rng, p, rows, m_cols, n_chips)
    b = _random_stack(rng, p, rows, m_cols, n_chips)
    seg, l2c = crossover_population(rng, a.segmentation, a.layer_to_chip,
                                    b.segmentation, b.layer_to_chip)
    assert seg.shape == a.segmentation.shape
    assert l2c.shape == a.layer_to_chip.shape
    for i in range(p):
        child = MappingEncoding(seg[i], l2c[i])
        assert is_legal(verify_encoding(child, n_chips))
        # each segmentation bit comes from one parent
        assert np.all((seg[i] == a.segmentation[i])
                      | (seg[i] == b.segmentation[i]))
        # each (row, segment) slice is inherited intact from one parent
        for lo, hi in child.segments():
            for r in range(rows):
                sl = l2c[i, r, lo:hi]
                assert (np.array_equal(sl, a.layer_to_chip[i, r, lo:hi])
                        or np.array_equal(sl, b.layer_to_chip[i, r, lo:hi]))


def test_crossover_population_deterministic():
    p, rows, m_cols = 16, 3, 10
    a = _random_stack(np.random.default_rng(1), p, rows, m_cols, 8)
    b = _random_stack(np.random.default_rng(2), p, rows, m_cols, 8)
    s1, l1 = crossover_population(np.random.default_rng(7), a.segmentation,
                                  a.layer_to_chip, b.segmentation,
                                  b.layer_to_chip)
    s2, l2 = crossover_population(np.random.default_rng(7), a.segmentation,
                                  a.layer_to_chip, b.segmentation,
                                  b.layer_to_chip)
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(l1, l2)


@pytest.mark.parametrize("progress", [0.0, 0.5, 1.0])
def test_mutate_population_validity_and_determinism(progress):
    rng = np.random.default_rng(3)
    pop = _random_stack(rng, 32, 4, 10, HW.n_chiplets)
    ref_seg = pop.segmentation.copy()
    ref_l2c = pop.layer_to_chip.copy()

    mutate_population(np.random.default_rng(11), pop, HW.n_chiplets,
                      progress, rate=0.9)
    for enc in pop.to_encodings():
        assert is_legal(verify_encoding(enc, HW.n_chiplets))

    pop2 = StackedPopulation(ref_seg.copy(), ref_l2c.copy())
    mutate_population(np.random.default_rng(11), pop2, HW.n_chiplets,
                      progress, rate=0.9)
    np.testing.assert_array_equal(pop.segmentation, pop2.segmentation)
    np.testing.assert_array_equal(pop.layer_to_chip, pop2.layer_to_chip)


def test_mutate_population_distribution_matches_per_individual():
    """Same rng family, same operator probabilities: the vectorised path's
    per-individual change statistics match looping ``mutate``."""
    p, rows, m_cols, n_chips = 400, 4, 12, HW.n_chiplets
    progress = 0.5

    def changed_cells(seg0, l2c0, seg1, l2c1):
        return ((seg0 != seg1).sum(axis=-1)
                + (l2c0 != l2c1).reshape(p, -1).sum(axis=-1))

    rng = np.random.default_rng(5)
    base = _random_stack(rng, p, rows, m_cols, n_chips)

    vec = StackedPopulation(base.segmentation.copy(),
                            base.layer_to_chip.copy())
    mutate_population(np.random.default_rng(6), vec, n_chips, progress,
                      rate=1.0)
    vec_changed = changed_cells(base.segmentation, base.layer_to_chip,
                                vec.segmentation, vec.layer_to_chip)

    ref_rng = np.random.default_rng(7)
    ref = [MappingEncoding(base.segmentation[i].copy(),
                           base.layer_to_chip[i].copy()) for i in range(p)]
    for enc in ref:
        mutate(ref_rng, enc, n_chips, progress)
    ref_changed = changed_cells(
        base.segmentation, base.layer_to_chip,
        np.stack([e.segmentation for e in ref]),
        np.stack([e.layer_to_chip for e in ref]))

    # same operator mix => same change-footprint distribution (loose CI)
    assert abs(vec_changed.mean() - ref_changed.mean()) \
        < 0.25 * max(ref_changed.mean(), 1.0)
    assert abs((vec_changed > 0).mean() - (ref_changed > 0).mean()) < 0.15


def test_ga_search_stacked_eval_path():
    """ga_search feeds the stacked population straight to an
    accepts_stacked eval_fn and still improves the objective."""
    g = build_execution_graph(SPEC, [prefill_request(64 * (i + 1))
                                     for i in range(4)], 2, tp=2, n_blocks=1)
    t = CostTables.build(g, HW)
    calls = {"stacked": 0}

    def eval_fn(pop):
        assert isinstance(pop, StackedPopulation)
        calls["stacked"] += 1
        return np.array([evaluate(g, e, HW, t).edp
                         for e in pop.to_encodings()])

    eval_fn.accepts_stacked = True
    res = ga_search(eval_fn, g.rows, g.n_cols, HW.n_chiplets,
                    GAConfig(population=12, generations=4, seed=0))
    assert calls["stacked"] == 5            # init + one per generation
    assert res.best_score <= res.history[0]
    assert is_legal(verify_encoding(res.best, HW.n_chiplets))
