"""repro.telemetry: counters, spans and compile attribution, and that
tracing the search and the service changes none of their results."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import cache_stats, compass
from repro.core.ga import GAConfig
from repro.core.hardware import make_hardware
from repro.core.workload import LLMSpec, decode_request, prefill_request


@pytest.fixture()
def traced():
    telemetry.drain()
    telemetry.enable()
    try:
        yield telemetry
    finally:
        telemetry.disable()
        telemetry.drain()


def test_disabled_span_is_the_shared_no_op_and_records_nothing():
    telemetry.disable()
    telemetry.drain()
    before = telemetry.span_totals()
    sp = telemetry.span("repro.test.off", rid=3)
    assert sp is telemetry.NO_SPAN
    assert telemetry.span("repro.test.other") is sp
    with sp as inner:
        assert not inner
        inner.set(hits=1)
    assert telemetry.drain() == []
    assert telemetry.span_totals() == before


def test_enabled_spans_nest_with_parents_and_self_time(traced):
    with telemetry.span("repro.test.outer") as outer:
        assert outer
        with telemetry.span("repro.test.inner", rid=7) as inner:
            inner.set(hits=2)
        with telemetry.span("repro.test.inner"):
            pass
    recs = telemetry.drain()
    assert [r.name for r in recs] == ["repro.test.inner", "repro.test.inner",
                                      "repro.test.outer"]
    a, b, top = recs
    assert top.parent is None
    assert a.parent == top.sid and b.parent == top.sid
    assert a.attrs == {"rid": 7, "hits": 2} and b.attrs == {}
    assert top.t0_ns <= a.t0_ns <= a.t1_ns <= b.t0_ns <= b.t1_ns <= top.t1_ns
    tot = telemetry.span_totals()
    inner_ns = (a.t1_ns - a.t0_ns) + (b.t1_ns - b.t0_ns)
    assert tot["repro.test.inner"]["count"] >= 2
    o = tot["repro.test.outer"]
    assert o["total_ns"] - o["self_ns"] >= inner_ns
    assert telemetry.drain() == []


def test_counters_and_high_water():
    telemetry.reset()
    snap = telemetry.snapshot()
    assert snap["services_started"] == 0
    assert snap["eval.order_hits"] == snap["eval.order_misses"] == 0
    for gone in ("preempts", "evictions", "transfer_pool_hits",
                 "transfer_pool_misses", "prefill_entrypoints",
                 "decode_entrypoints"):
        assert gone not in snap
    telemetry.bump("iterations")
    telemetry.bump("iterations", 4)
    telemetry.bump("repro.test.new")
    telemetry.high_water("peak_queue_depth", 5)
    telemetry.high_water("peak_queue_depth", 3)
    snap = telemetry.snapshot()
    assert snap["iterations"] == 5 and snap["repro.test.new"] == 1
    assert snap["peak_queue_depth"] == 5
    snap["iterations"] = 99                  # a snapshot is a copy
    assert telemetry.snapshot()["iterations"] == 5
    telemetry.reset()
    assert telemetry.snapshot()["iterations"] == 0
    assert "repro.test.new" not in telemetry.snapshot()


def test_a_compile_under_a_span_counts_against_it(traced):
    before = telemetry.snapshot()
    with telemetry.span("repro.test.compile"):
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(5.0))
    after = telemetry.snapshot()
    assert after["compiles"] > before["compiles"]
    assert after.get("compiles.repro.test.compile", 0) >= 1


def test_tracing_follows_a_profiler_session(tmp_path):
    telemetry.disable()
    telemetry.drain()
    assert not telemetry.follow_profiler()
    with jax.profiler.trace(str(tmp_path)):
        assert telemetry.follow_profiler()
        with telemetry.span("repro.test.profiled"):
            pass
    assert not telemetry.follow_profiler()
    assert telemetry.span("repro.test.after") is telemetry.NO_SPAN
    assert [r.name for r in telemetry.drain()] == ["repro.test.profiled"]


def _search():
    spec = LLMSpec("dense", 256, 4, 4, 64, 1024, 1000, 8)
    hw = make_hardware(64, "M", layout=None, tensor_parallel=2)
    hw = hw.replace(layout=tuple(["WS", "OS"] * (hw.n_chiplets // 2)))
    batches = [[prefill_request(128), prefill_request(64), decode_request(300)],
               [prefill_request(30), prefill_request(31), decode_request(77)],
               [decode_request(40), decode_request(90)]]
    out = compass.search_mapping(spec, batches, hw, [2, 2, 2],
                                 GAConfig(population=8, generations=3, seed=5),
                                 objective="edp", n_blocks=2)
    h = hashlib.sha256()
    for r in out.ga_results:
        h.update(np.asarray(r.history, dtype=np.float64).tobytes())
    return h.hexdigest(), out.score, out.batch_latencies


def test_search_is_bit_identical_traced_and_cache_stats_show_it():
    telemetry.disable()
    off = _search()
    telemetry.drain()
    telemetry.enable()
    try:
        on = _search()
    finally:
        telemetry.disable()
    assert off[0] == on[0] and off[1] == on[1]
    np.testing.assert_array_equal(off[2], on[2])
    recs = telemetry.drain()
    names = {r.name for r in recs}
    assert {"repro.search", "repro.search.setup", "repro.search.oracle",
            "repro.ga.step", "repro.ga.score", "repro.eval",
            "repro.eval.orders", "repro.eval.dispatch",
            "repro.eval.fetch"} <= names
    by_sid = {r.sid: r for r in recs}
    for r in recs:
        if r.name == "repro.eval.fetch":
            assert by_sid[r.parent].name == "repro.eval"
        if r.name == "repro.ga.step":
            assert by_sid[r.parent].name == "repro.search"
    orders = [r for r in recs if r.name == "repro.eval.orders"]
    assert all(r.attrs["hits"] + r.attrs["misses"] == 8 for r in orders)
    stats = cache_stats()["telemetry"]
    assert stats["counters"]["eval.order_hits"] > 0
    assert stats["spans"]["repro.eval"]["count"] >= len(
        [r for r in recs if r.name == "repro.eval"])


def test_served_tokens_are_bit_identical_traced():
    from repro.configs import all_archs
    from repro.models import init_model
    from repro.serving import SCHEDULERS, AsyncLLMService, ServiceConfig
    from repro.serving.service import golden_parity_stream, service_requests

    cfg = all_archs()["qwen1.5-0.5b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    stream = golden_parity_stream()
    svc = AsyncLLMService(params, cfg, ServiceConfig(max_batch=3, max_len=64,
                                                     block_len=16))

    def serve():
        reqs = service_requests(stream, cfg.vocab)
        res = svc.serve_sync(reqs, SCHEDULERS["chunked_prefill"](chunk=8))
        return [list(r.generated) for r in res.requests], res

    telemetry.disable()
    off, _ = serve()
    telemetry.drain()
    telemetry.enable()
    try:
        on, res = serve()
    finally:
        telemetry.disable()
    assert off == on
    recs = telemetry.drain()
    by_sid = {r.sid: r for r in recs}
    steps = [r for r in recs if r.name == "repro.serve.decode"]
    assert steps
    for s in steps:
        assert by_sid[s.parent].name == "repro.serve.iter.decode"
        assert by_sid[by_sid[s.parent].parent].name == "repro.serve.iter"
    kids = {r.name for r in recs if r.parent in {s.sid for s in steps}}
    assert kids == {"repro.serve.decode.stage", "repro.serve.decode.dispatch",
                    "repro.serve.decode.fetch"}
    rids = {r.attrs["rid"] for r in recs if r.name == "repro.serve.prefill"}
    assert rids == {r.rid for r in res.requests}
    for ev in res.wall_events.values():
        assert ev["arrival_s"] <= ev["admit_s"] <= ev["first_s"] <= ev["done_s"]
