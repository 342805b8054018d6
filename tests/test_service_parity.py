"""The sim-to-real contract: under the deterministic iteration clock the
async paged service must replay ``plan_rollout`` *exactly* — admission
order, per-iteration batch membership and RequestTimings bit-identical for
every scheduler — and generate the same tokens as the dense engine.
"""
import warnings

import jax
import numpy as np
import pytest

from repro.configs import all_archs
from repro.core.streams import RequestStream, StreamRequest, rollout
from repro.models import init_model
from repro.serving import (
    SCHEDULERS,
    AsyncLLMService,
    ServeRequest,
    ServiceConfig,
    ServingEngine,
)
from repro.serving.scheduler import plan_rollout
from repro.serving.service import golden_parity_stream, service_requests

CFG = all_archs()["qwen1.5-0.5b"].reduced()
PARAMS = init_model(jax.random.PRNGKey(0), CFG)
STREAM = golden_parity_stream()
SCHED_NAMES = ["vllm", "orca", "chunked_prefill"]
MAX_BATCH, MAX_LEN = 3, 64


def _sched(name):
    return (SCHEDULERS[name](chunk=8) if name == "chunked_prefill"
            else SCHEDULERS[name]())


def _fresh_requests():
    return service_requests(STREAM, CFG.vocab)


@pytest.fixture(scope="module")
def served():
    """One deterministic-clock serve per scheduler (shared across the
    module: the service compile cost is paid once)."""
    out = {}
    for name in SCHED_NAMES:
        svc = AsyncLLMService(
            PARAMS, CFG,
            ServiceConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                          block_len=16))
        out[name] = svc.serve_sync(_fresh_requests(), _sched(name),
                                   stream_name=STREAM.name)
    return out


@pytest.mark.parametrize("name", SCHED_NAMES)
def test_measured_rollout_matches_planned_bitwise(served, name):
    """Batches, arrival/first/done indices, token counts and the priced
    RequestTimings of the *measured* schedule equal the planner's — bit
    for bit."""
    res = served[name]
    assert not res.truncated and not res.unfinished
    ro = rollout(STREAM, _sched(name), max_slots=MAX_BATCH, max_iters=10_000)
    assert res.rollout.batches == ro.batches
    np.testing.assert_array_equal(res.rollout.arrival_b, ro.arrival_b)
    np.testing.assert_array_equal(res.rollout.first_b, ro.first_b)
    np.testing.assert_array_equal(res.rollout.done_b, ro.done_b)
    np.testing.assert_array_equal(res.rollout.n_new_tokens, ro.n_new_tokens)
    lat = np.linspace(0.01, 0.02, len(ro.batches))
    planned, measured = ro.timings(lat), res.timings(lat)
    np.testing.assert_array_equal(planned.ttft_s, measured.ttft_s)
    np.testing.assert_array_equal(planned.tpot_s, measured.tpot_s)
    np.testing.assert_array_equal(planned.finished, measured.finished)
    assert planned.makespan_s == measured.makespan_s


@pytest.mark.parametrize("name", SCHED_NAMES)
def test_admission_log_matches_plan_rollout(served, name):
    """(rid, slot, iteration) admission triples in the exact order the
    pure planner admits — the queueing layer adds no reordering."""
    reqs = [ServeRequest(r.rid, list(r.prompt), r.max_new_tokens,
                         arrived_iter=r.arrived_iter)
            for r in _fresh_requests()]
    planned = []
    for it, plan in plan_rollout(reqs, _sched(name), MAX_BATCH, 10_000):
        for req, _ in plan.prefill:
            if req.prefilled == 0:        # yield-time state: new admission
                planned.append((req.rid, req.slot, it))
    assert served[name].admissions == planned


@pytest.mark.parametrize("name", SCHED_NAMES)
def test_tokens_match_dense_engine(served, name):
    """Greedy tokens through the paged service equal the dense engine's —
    stale-block reads are fully masked."""
    eng = ServingEngine(PARAMS, CFG, max_batch=MAX_BATCH, max_len=MAX_LEN)
    fin, _ = eng.run(_fresh_requests(), _sched(name))
    assert {r.rid: r.generated for r in fin} == \
        {r.rid: r.generated for r in served[name].finished}


def test_block_exhaustion_queues_not_corrupts(served):
    """num_blocks far below peak demand: admissions must *wait* for blocks
    (never corrupt another request's KV) and every request still finishes
    with exactly the tokens of the un-starved run."""
    svc = AsyncLLMService(
        PARAMS, CFG,
        ServiceConfig(max_batch=MAX_BATCH, max_len=MAX_LEN, block_len=16,
                      num_blocks=5))      # 4 usable blocks << 3 slots' worth
    res = svc.serve_sync(_fresh_requests(), _sched("vllm"),
                         stream_name=STREAM.name)
    assert not res.truncated
    assert len(res.finished) == STREAM.n_requests
    assert sum(s.blocked_admissions for s in res.stats) > 0
    assert max(s.blocks_used for s in res.stats) <= 4
    assert {r.rid: r.generated for r in res.finished} == \
        {r.rid: r.generated for r in served["vllm"].finished}
    # and the schedule genuinely degraded vs. the unconstrained run
    assert len(res.stats) >= len(served["vllm"].stats)


def test_service_reuse_over_stale_pools(served):
    """A second serve() on the same instance reuses the (now garbage-laden)
    pools without zeroing them — stale blocks must be invisible."""
    svc = AsyncLLMService(
        PARAMS, CFG,
        ServiceConfig(max_batch=MAX_BATCH, max_len=MAX_LEN, block_len=16))
    first = svc.serve_sync(_fresh_requests(), _sched("vllm"),
                           stream_name=STREAM.name)
    again = svc.serve_sync(_fresh_requests(), _sched("vllm"),
                           stream_name=STREAM.name)
    assert {r.rid: r.generated for r in again.finished} == \
        {r.rid: r.generated for r in first.finished}


def test_service_truncation_reports_unfinished():
    """An exhausted iteration budget surfaces in-flight requests instead of
    dropping them."""
    svc = AsyncLLMService(
        PARAMS, CFG,
        ServiceConfig(max_batch=MAX_BATCH, max_len=MAX_LEN, max_iters=3))
    with pytest.warns(UserWarning, match="truncated"):
        res = svc.serve_sync(_fresh_requests(), _sched("vllm"))
    assert res.truncated
    assert res.unfinished
    assert len(res.finished) + len(res.unfinished) == STREAM.n_requests
    assert res.summary()["unfinished"] == len(res.unfinished)


def _warm_mixed_stream():
    """Cold and warm (decode-resident) arrivals interleaved, with slot
    contention (4 requests, 3 slots)."""
    reqs = [
        StreamRequest(10, 3, 0),
        StreamRequest(6, 2, 1, warm_context=9),
        StreamRequest(8, 4, 2),
        StreamRequest(5, 3, 2, warm_context=14),
    ]
    return RequestStream.from_requests(reqs, name="warm-mixed")


def test_warm_mixed_service_parity_and_warm_mask():
    """Regression (warm-mask loss): the service used to hardcode
    ``warm=zeros`` in its measured rollout and wall timings, leaking warm
    decode-resident requests — whose TTFT is undefined — into
    ``cold_ttft_s``. Warm requests now ride the measured path (context
    prefaulted into KV at admission) and the measured schedule, warm mask
    included, must equal the planner's bit for bit."""
    stream = _warm_mixed_stream()
    svc = AsyncLLMService(
        PARAMS, CFG,
        ServiceConfig(max_batch=MAX_BATCH, max_len=MAX_LEN, block_len=16))
    res = svc.serve_sync(service_requests(stream, CFG.vocab),
                         _sched("orca"), stream_name=stream.name)
    assert not res.truncated and not res.unfinished
    assert res.counters["warm_requests"] == 2
    ro = rollout(stream, _sched("orca"), max_slots=MAX_BATCH,
                 max_iters=10_000)
    assert res.rollout.batches == ro.batches
    np.testing.assert_array_equal(res.rollout.warm, ro.warm)
    np.testing.assert_array_equal(res.rollout.arrival_b, ro.arrival_b)
    np.testing.assert_array_equal(res.rollout.first_b, ro.first_b)
    np.testing.assert_array_equal(res.rollout.done_b, ro.done_b)
    np.testing.assert_array_equal(res.rollout.n_new_tokens, ro.n_new_tokens)
    lat = np.linspace(0.01, 0.02, len(ro.batches))
    planned, measured = ro.timings(lat), res.timings(lat)
    np.testing.assert_array_equal(planned.ttft_s, measured.ttft_s)
    np.testing.assert_array_equal(planned.tpot_s, measured.tpot_s)
    # the warm mask is real, so cold_ttft_s excludes the warm requests
    assert measured.warm.sum() == 2
    assert measured.cold_ttft_s.shape[-1] == 2
    assert np.isfinite(measured.cold_ttft_s).all()
    wall = res.wall_timings()
    np.testing.assert_array_equal(wall.warm, ro.warm)
    assert wall.cold_ttft_s.shape[-1] == 2


def test_occupancy_stats_and_counters(served):
    res = served["vllm"]
    assert all(0 <= s.slots_used <= MAX_BATCH for s in res.stats)
    assert any(s.slots_used > 1 for s in res.stats)
    assert max(s.blocks_used for s in res.stats) == \
        res.counters["blocks_peak_used"]
    assert res.counters["transfer_pool_hits"] > 0        # buffers recycled
    assert res.counters["admissions"] == STREAM.n_requests
    # SHARK-style bucketed entry points: powers of two only
    for b in res.counters["decode_entrypoints"]:
        assert b & (b - 1) == 0
    s = res.summary()
    assert s["requests"] == STREAM.n_requests
    assert s["mean_slots_used"] > 0
    from repro.core.observability import cache_stats
    serving = cache_stats()["telemetry"]["counters"]
    assert serving["services_started"] >= 1
    assert serving["prefill_tokens"] > 0


def test_wall_clock_service_completes():
    """The same service under a real clock (arrivals in wall time): every
    request finishes and wall timings are sane (no schedule parity claim)."""
    from repro.serving import WallClock
    svc = AsyncLLMService(
        PARAMS, CFG,
        ServiceConfig(max_batch=MAX_BATCH, max_len=MAX_LEN),
        clock=WallClock(period_s=0.005))
    res = svc.serve_sync(_fresh_requests(), _sched("vllm"))
    assert len(res.finished) == STREAM.n_requests
    wt = res.wall_timings()
    assert wt.finished.all()
    assert np.isfinite(wt.ttft_s).all() and (wt.ttft_s >= 0).all()
    assert wt.makespan_s > 0


def test_mamba_service_matches_engine():
    """Recurrent (slot-state) layers ride the paged service too: tokens
    match the dense engine on a hybrid-free mamba arch."""
    cfg = all_archs()["mamba2-2.7b"].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    reqs = service_requests(STREAM, cfg.vocab)[:4]
    svc = AsyncLLMService(params, cfg,
                          ServiceConfig(max_batch=2, max_len=MAX_LEN))
    res = svc.serve_sync([ServeRequest(r.rid, list(r.prompt),
                                       r.max_new_tokens,
                                       arrived_iter=r.arrived_iter)
                          for r in reqs], _sched("orca"))
    eng = ServingEngine(params, cfg, max_batch=2, max_len=MAX_LEN)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fin, _ = eng.run(reqs, _sched("orca"))
    assert {r.rid: r.generated for r in fin} == \
        {r.rid: r.generated for r in res.finished}


def test_cold_passes_block_starved_warm_head():
    """Regression (service head-of-line blocking): a warm request whose
    context cannot reserve its KV blocks used to pin every later cold
    arrival in the pending queue. The cold request must be admitted past
    the blocked warm head (warm admission waits for blocks; cold work
    proceeds), and everything still finishes uncorrupted."""
    svc = AsyncLLMService(
        PARAMS, CFG,
        ServiceConfig(max_batch=MAX_BATCH, max_len=MAX_LEN, block_len=16,
                      num_blocks=4))        # 3 usable blocks = 48 tokens
    reqs = [
        # cold R0: demand 24 tokens (2 blocks), admitted at iter 0
        ServeRequest(0, list(range(20)), 4, arrived_iter=0),
        # warm W: demand 43 tokens (3 blocks) -> blocked behind R0
        ServeRequest(1, list(range(40)), 3, prefilled=40, arrived_iter=1),
        # cold C: demand 10 tokens (1 block) -> must pass W
        ServeRequest(2, list(range(8)), 2, arrived_iter=2),
    ]
    res = svc.serve_sync(reqs, _sched("orca"))
    assert not res.truncated and len(res.finished) == 3
    admitted = {rid: it for rid, _slot, it in res.admissions}
    assert admitted[2] < admitted[1], (
        "cold request must not wait behind the block-starved warm head: "
        f"admissions {res.admissions}")
    assert sum(s.blocked_admissions for s in res.stats) > 0
    assert res.counters["warm_requests"] == 1
