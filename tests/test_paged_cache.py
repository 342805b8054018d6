"""Paged KV residency invariants: allocator ownership/conservation,
gather-vs-dense bitwise equality, admission at exhaustion, buffer pooling.

Property tests run under the offline hypothesis shim (keyword scalar
strategies; sequences are derived from drawn seeds)."""
from types import SimpleNamespace

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving.paged_cache import (
    BlockAllocator,
    PagedKVCache,
    TransferBufferPool,
)


def _check_invariants(alloc: BlockAllocator):
    """No double ownership, no null-block ownership, exact free-list
    conservation: owned + free == {1..num_blocks-1}."""
    owned = []
    for blocks in alloc.owners().values():
        owned.extend(blocks)
    assert len(owned) == len(set(owned)), "block owned twice"
    assert 0 not in owned, "null block handed out"
    assert set(owned) | set(alloc._free) == set(range(1, alloc.num_blocks))
    assert len(owned) + alloc.blocks_free == alloc.capacity


@given(seed=st.integers(0, 10_000), num_blocks=st.integers(2, 40),
       block_len=st.integers(1, 32))
@settings(max_examples=40, deadline=None)
def test_allocator_random_walk_invariants(seed, num_blocks, block_len):
    """Arbitrary interleavings of reserve/free keep every block owned by at
    most one request and conserve the free list exactly."""
    rng = np.random.default_rng(seed)
    alloc = BlockAllocator(num_blocks, block_len)
    live: list[int] = []
    next_rid = 0
    for _ in range(60):
        if live and (rng.random() < 0.4 or alloc.blocks_free == 0):
            rid = live.pop(int(rng.integers(len(live))))
            alloc.free(rid)
        else:
            demand = int(rng.integers(0, 3 * block_len + 1))
            could = alloc.can_reserve(demand)
            ok = alloc.reserve(next_rid, demand)
            assert ok == could
            if ok:
                assert len(alloc.table(next_rid)) == alloc.blocks_for(demand)
                live.append(next_rid)
            next_rid += 1
        _check_invariants(alloc)
    for rid in live:
        alloc.free(rid)
    assert alloc.blocks_free == alloc.capacity


@given(num_blocks=st.integers(2, 30), block_len=st.integers(1, 16),
       demand=st.integers(1, 200))
@settings(max_examples=40, deadline=None)
def test_admission_blocks_at_exhaustion(num_blocks, block_len, demand):
    """When the free list cannot cover the demand, reserve() refuses (an
    OOM event) and mutates nothing; it succeeds verbatim after space is
    freed."""
    alloc = BlockAllocator(num_blocks, block_len)
    need = alloc.blocks_for(demand)
    filler = []
    rid = 0
    while alloc.blocks_free >= need:      # fill until demand can't fit
        assert alloc.reserve(rid, block_len)
        filler.append(rid)
        rid += 1
    before_free = alloc.blocks_free
    before_oom = alloc.oom_events
    assert not alloc.can_reserve(demand)
    assert alloc.reserve(999, demand) is False
    assert alloc.oom_events == before_oom + 1
    assert alloc.blocks_free == before_free
    assert 999 not in alloc.owners()
    _check_invariants(alloc)
    freed = 0
    while freed < need and filler:        # free just enough, retry
        freed += alloc.free(filler.pop())
    if freed >= need:
        assert alloc.reserve(999, demand) is True
        _check_invariants(alloc)


def test_allocator_rejects_double_reserve_and_null_config():
    alloc = BlockAllocator(8, 4)
    assert alloc.reserve(1, 4)
    with pytest.raises(ValueError, match="already holds"):
        alloc.reserve(1, 4)
    with pytest.raises(ValueError):
        BlockAllocator(1, 4)              # only the null block: unusable
    with pytest.raises(ValueError):
        BlockAllocator(8, 0)


@given(seed=st.integers(0, 10_000), block_len=st.sampled_from([1, 2, 4, 8]),
       t=st.integers(1, 6))
@settings(max_examples=25, deadline=None)
def test_gather_matches_dense_slicing_bitwise(seed, block_len, t):
    """Gathering a request's blocks reproduces the dense cache row
    *bitwise* — the exactness the parity contract stands on. Pools here are
    synthetic numpy payloads; no model involved."""
    from repro.models.paged import gather_paged_cache
    rng = np.random.default_rng(seed)
    num_blocks, heads, dim, n = 12, 2, 3, 2
    pool = rng.standard_normal((num_blocks, block_len, heads, dim))
    pool = np.asarray(pool, np.float32)
    tables = rng.integers(0, num_blocks, size=(n, t)).astype(np.int32)
    lens = rng.integers(0, t * block_len + 1, size=(n,)).astype(np.int32)
    slots = np.zeros((n,), np.int32)
    cfg = SimpleNamespace(n_kv_heads=heads, head_dim=dim)
    [out] = gather_paged_cache([{"k": pool}], tables, lens, slots, cfg)
    dense = np.stack([
        np.concatenate([pool[b] for b in tables[j]], axis=0)
        for j in range(n)])
    assert np.array_equal(np.asarray(out["k"]), dense)
    assert np.array_equal(np.asarray(out["len"]), lens)


def test_transfer_buffer_pool_reuse_and_bound():
    pool = TransferBufferPool(capacity=2)
    a = pool.acquire((4,), np.int32)
    b = pool.acquire((4,), np.int32)
    assert pool.misses == 2 and pool.hits == 0
    assert a is not b
    pool.release(a)
    c = pool.acquire((4,), np.int32)
    assert c is a and pool.hits == 1
    assert pool.acquire((4, 2), np.int32).shape == (4, 2)  # distinct key
    # capacity bound: a third release of the same key is dropped
    x, y, z = (np.empty((4,), np.int32) for _ in range(3))
    for buf in (x, y, z):
        pool.release(buf)
    assert len(pool._pools[((4,), np.dtype(np.int32).str)]) == 2


def test_paged_kv_cache_validates_and_binds():
    pytest.importorskip("jax")
    from repro.configs import all_archs
    cfg = all_archs()["qwen1.5-0.5b"].reduced()
    with pytest.raises(ValueError, match="multiple of block_len"):
        PagedKVCache(cfg, max_batch=2, max_len=50, block_len=16)
    kv = PagedKVCache(cfg, max_batch=2, max_len=64, block_len=16)
    assert kv.blocks_per_seq == 4
    assert kv.allocator.capacity == 2 * 4          # full residency default
    assert kv.capacity_tokens() == 128
    assert kv.allocator.reserve(7, 33)             # 3 blocks
    kv.bind(0, 7)
    row = kv.tables_np[0]
    assert (row[:3] > 0).all() and (row[3:] == 0).all()
    assert kv.lens_np[0] == 0
    kv.release(0, 7)
    assert (kv.tables_np[0] == 0).all()
    assert kv.allocator.blocks_free == kv.allocator.capacity
    assert kv.resident_bytes() > 0


def _decode_by_gathering(params, cfg, tokens, pools, tables, lens, slots,
                         block_len):
    """The decode with every layer over the gathered dense view: the stock
    ``decode_step``, then the one written position scattered back."""
    import jax.numpy as jnp

    from repro.models.paged import gather_paged_cache, is_slot_layer
    from repro.models.transformer import decode_step

    n = tokens.shape[0]
    cache = gather_paged_cache(pools, tables, lens, slots, cfg)
    logits, new_cache = decode_step(params, cfg, tokens, cache)
    bidx = jnp.take_along_axis(tables, (lens // block_len)[:, None], 1)[:, 0]
    off = lens % block_len
    out = []
    for layer, new in zip(pools, new_cache):
        if is_slot_layer(layer):
            out.append({k: v.at[slots].set(new[k]) for k, v in layer.items()})
            continue
        d = {}
        for k, pool in layer.items():
            idx = lens.reshape((n,) + (1,) * (new[k].ndim - 1))
            upd = jnp.take_along_axis(new[k], idx, axis=1)[:, 0]
            d[k] = pool.at[bidx, off].set(
                upd.reshape((n,) + pool.shape[2:]).astype(pool.dtype))
        out.append(d)
    return jnp.argmax(logits, -1), out


def _kernel_calls(jaxpr) -> int:
    """Calls of the paged decode kernel's entry in a (nested) jaxpr."""
    import jax

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") == "paged_decode_attention":
            n += 1
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    n += _kernel_calls(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    n += _kernel_calls(sub)
    return n


# (arch, int8 KV, attention layers read in place by the kernel)
ROUTES = [("qwen1.5-0.5b", False, 2), ("qwen2-1.5b", False, 2),
          ("deepseek-v2-236b", False, 0), ("jamba-v0.1-52b", False, 1),
          ("qwen1.5-0.5b", True, 0)]


@pytest.mark.parametrize("arch,int8,in_place", ROUTES)
def test_paged_decode_matches_gather_path(monkeypatch, arch, int8, in_place):
    """Three greedy decode steps over stale, garbage-filled pools: float
    MHA/GQA layers read the pool in place through the kernel, MLA latents,
    int8 pools and recurrent state go through ``gather_paged_cache``. Each
    step's tokens equal those of the all-gather decode from the same pools.
    The new pools equal its pools bitwise up to the first layer read in
    place (the write is the same scatter of the same values) and wherever
    the step writes nothing; past that layer the written rows carry the
    kernel's float32 rounding of the attention above them."""
    import jax
    import jax.numpy as jnp

    from repro.configs import all_archs
    from repro.models import init_model
    from repro.models.paged import (NULL_BLOCK, init_paged_pools,
                                    is_slot_layer, paged_decode,
                                    reads_in_place)

    if int8:
        monkeypatch.setenv("REPRO_CACHE_QUANT", "1")
    cfg = all_archs()[arch].reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    max_batch, block_len, t = 3, 8, 4
    num_blocks = max_batch * t + 1
    rng = np.random.default_rng(7)
    pools = jax.tree.map(
        lambda a: jnp.asarray(
            rng.integers(-127, 128, a.shape) if a.dtype == jnp.int8
            else rng.normal(size=a.shape), a.dtype),
        init_paged_pools(cfg, max_batch, num_blocks, block_len))
    flags = [reads_in_place(p) for p in pools]
    assert sum(flags) == in_place
    exact_to = flags.index(True) if in_place else len(pools)
    # two live requests and a padding lane (null table, scratch slot)
    tables = np.full((3, t), NULL_BLOCK, np.int32)
    tables[:2] = rng.permutation(np.arange(1, num_blocks))[:8].reshape(2, t)
    lens = np.array([5, 2 * block_len - 2, 0], np.int32)
    slots = np.array([0, 2, max_batch], np.int32)
    tokens = jnp.asarray([3, 11, 0], jnp.int32)

    kernel = jax.jit(lambda tok, p, ln: paged_decode(
        params, cfg, tok, p, jnp.asarray(tables), ln, jnp.asarray(slots),
        block_len))
    gather = jax.jit(lambda tok, p, ln: _decode_by_gathering(
        params, cfg, tok, p, jnp.asarray(tables), ln, jnp.asarray(slots),
        block_len))
    assert _kernel_calls(jax.make_jaxpr(kernel)(
        tokens, pools, jnp.asarray(lens)).jaxpr) == in_place
    for step in range(3):
        ln = lens + step * np.array([1, 1, 0], np.int32)
        got_tok, got = kernel(tokens, pools, jnp.asarray(ln))
        tokens, pools = gather(tokens, pools, jnp.asarray(ln))
        np.testing.assert_array_equal(np.asarray(got_tok), np.asarray(tokens))
        bidx = tables[np.arange(3), ln // block_len]
        for i, (a_layer, b_layer) in enumerate(zip(got, pools)):
            for k in b_layer:
                a, b = np.asarray(a_layer[k]), np.asarray(b_layer[k])
                if i <= exact_to:
                    np.testing.assert_array_equal(a, b)
                    continue
                written = np.zeros(b.shape[:2], bool)
                if is_slot_layer(b_layer):
                    written[slots] = True
                else:
                    written[bidx, ln % block_len] = True
                np.testing.assert_array_equal(a[~written], b[~written])
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
