"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,hq,hkv,lq,lk,d,causal", [
    (1, 4, 4, 64, 64, 64, True),
    (2, 8, 2, 96, 160, 64, True),    # GQA + longer KV (cached prefix)
    (1, 6, 3, 33, 57, 32, False),    # ragged, bidirectional
    (1, 2, 1, 128, 128, 128, True),  # MXU-aligned
])
def test_flash_attention(b, hq, hkv, lq, lk, d, causal, dtype, tol):
    q, k, v = (_rand((b, hq, lq, d), dtype), _rand((b, hkv, lk, d), dtype),
               _rand((b, hkv, lk, d), dtype))
    out = ops.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    expect = ref.flash_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 8, 2, 257, 64),
    (1, 4, 4, 96, 32),
    (3, 4, 1, 130, 64),   # MLA-style single shared KV head
])
def test_decode_attention(b, hq, hkv, s, d, dtype, tol):
    q = _rand((b, hq, d), dtype)
    kc = _rand((b, s, hkv, d), dtype)
    vc = _rand((b, s, hkv, d), dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, size=b), jnp.int32)
    out = ops.decode_attention(q, kc, vc, lens, block_s=64)
    expect = ref.decode_attention_reference(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 96, 2, 16, 8, 32),
    (2, 70, 3, 8, 16, 32),   # ragged length vs chunk
    (1, 128, 1, 32, 32, 64),
])
def test_ssd_scan(b, l, h, p, n, chunk):
    x = _rand((b, l, h, p), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, l, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    bm = _rand((b, l, n), jnp.float32)
    cm = _rand((b, l, n), jnp.float32)
    y, s_fin = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk)
    y_ref, s_ref = ref.ssd_reference(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_ref),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), nb=st.integers(1, 3), pop=st.integers(1, 4),
       rows=st.integers(1, 3), cols=st.integers(2, 5), chips=st.integers(1, 4))
def test_mapping_eval_kernel(seed, nb, pop, rows, cols, chips):
    """Pallas kernel == sequential reference on randomized scheduled orders
    (chain dependencies within each row, random chip assignments)."""
    rng = np.random.default_rng(seed)
    t_len = rows * cols
    t_proc = rng.uniform(0.1, 1.0, size=(nb, pop, t_len)).astype(np.float32)
    chip = rng.integers(0, chips, size=(pop, t_len)).astype(np.int32)
    # per-individual random row interleaving of a per-row column chain
    ppos = np.zeros((pop, t_len, 1), dtype=np.int32)
    for p in range(pop):
        order = np.stack([np.repeat(np.arange(rows), cols),
                          np.tile(np.arange(cols), rows)], axis=1)
        order = order[rng.permutation(t_len)]
        # keep each row's columns in increasing order (valid schedule)
        for r in range(rows):
            sel = order[:, 0] == r
            order[sel, 1] = np.sort(order[sel, 1])
        pos = np.zeros((rows, cols), dtype=np.int32)
        pos[order[:, 0], order[:, 1]] = np.arange(t_len)
        for t, (r, c) in enumerate(order):
            ppos[p, t, 0] = pos[r, c - 1] if c > 0 else t_len
    end, free = ops.mapping_eval(jnp.asarray(t_proc), jnp.asarray(chip),
                                 jnp.asarray(ppos), chips)
    e_end, e_free = ref.mapping_eval_reference(t_proc, chip, ppos, chips)
    np.testing.assert_allclose(np.asarray(end), e_end, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(free), e_free, rtol=1e-5)


# ---------------------------------------------------------------------------
# Fused pass-A/pass-B megakernel
# ---------------------------------------------------------------------------


def _fused_case(seed, nb, pop, rows, cols, width, chips):
    """Random fused-kernel inputs: un-gathered (rows*cols)-flat cost rows,
    a random *permutation* sched_idx per individual (every cost cell used
    once, like a real schedule), random chips, random valid ppos."""
    rng = np.random.default_rng(seed)
    t_len = rows * cols
    t_proc = rng.uniform(0.1, 1.0, size=(nb, pop, t_len)).astype(np.float32)
    sched = np.stack([rng.permutation(t_len) for _ in range(pop)]
                     ).astype(np.int32)
    chip = rng.integers(0, chips, size=(pop, t_len)).astype(np.int32)
    ppos = np.full((pop, t_len, width), t_len, dtype=np.int32)
    for t in range(1, t_len):
        k = rng.integers(0, width + 1)
        if k:
            ppos[:, t, :k] = rng.integers(0, t, size=(pop, k))
    return t_proc, sched, chip, ppos


@pytest.mark.parametrize("grid_order", ["batch_major", "pop_major"])
@pytest.mark.parametrize("nb,pop", [(1, 3), (2, 5), (3, 1)])
def test_mapping_eval_fused_matches_unfused_and_reference(grid_order, nb,
                                                          pop):
    """The megakernel's in-kernel gather + recurrence is BITWISE the
    unfused kernel fed the pre-gathered tproc, under both grid orders and
    odd (non-multiple) population sizes; float64 reference to 1e-6."""
    chips = 4
    t_proc, sched, chip, ppos = _fused_case(nb * 10 + pop, nb, pop,
                                            rows=3, cols=5, width=2,
                                            chips=chips)
    end_f, free_f = ops.mapping_eval_fused(
        jnp.asarray(t_proc), jnp.asarray(sched), jnp.asarray(chip),
        jnp.asarray(ppos), chips, grid_order=grid_order)
    gathered = np.take_along_axis(
        t_proc, np.broadcast_to(sched[None], t_proc.shape), axis=-1)
    end_u, free_u = ops.mapping_eval(jnp.asarray(gathered),
                                     jnp.asarray(chip), jnp.asarray(ppos),
                                     chips)
    np.testing.assert_array_equal(np.asarray(end_f), np.asarray(end_u))
    np.testing.assert_array_equal(np.asarray(free_f), np.asarray(free_u))
    e_end, e_free = ref.mapping_eval_fused_reference(t_proc, sched, chip,
                                                     ppos, chips)
    np.testing.assert_allclose(np.asarray(end_f), e_end, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(free_f), e_free, rtol=1e-5)


def test_mapping_eval_fused_host_bitwise_matches_kernel():
    """The off-TPU fused XLA program and the interpreted megakernel are the
    same function bit for bit (same gather, same op order per step)."""
    chips = 3
    t_proc, sched, chip, ppos = _fused_case(7, 2, 4, rows=2, cols=6,
                                            width=3, chips=chips)
    args = (jnp.asarray(t_proc), jnp.asarray(sched), jnp.asarray(chip),
            jnp.asarray(ppos), chips)
    end_k, free_k = ops.mapping_eval_fused(*args)
    end_h, free_h = ops.mapping_eval_fused_host(*args)
    np.testing.assert_array_equal(np.asarray(end_k), np.asarray(end_h))
    np.testing.assert_array_equal(np.asarray(free_k), np.asarray(free_h))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), nb=st.integers(1, 3), pop=st.integers(1, 5),
       rows=st.integers(1, 3), cols=st.integers(2, 5), width=st.integers(1, 4),
       chips=st.integers(1, 4),
       grid_order=st.sampled_from(["batch_major", "pop_major"]))
def test_mapping_eval_fused_property(seed, nb, pop, rows, cols, width, chips,
                                     grid_order):
    """Property: for ANY random ppos layout (variable live-lane counts,
    sentinel-only steps included), fused == gather+unfused bitwise and
    == float64 reference to 1e-6."""
    t_proc, sched, chip, ppos = _fused_case(seed, nb, pop, rows, cols,
                                            width, chips)
    end_f, free_f = ops.mapping_eval_fused(
        jnp.asarray(t_proc), jnp.asarray(sched), jnp.asarray(chip),
        jnp.asarray(ppos), chips, grid_order=grid_order)
    gathered = np.take_along_axis(
        t_proc, np.broadcast_to(sched[None], t_proc.shape), axis=-1)
    end_u, free_u = ops.mapping_eval(jnp.asarray(gathered),
                                     jnp.asarray(chip), jnp.asarray(ppos),
                                     chips)
    np.testing.assert_array_equal(np.asarray(end_f), np.asarray(end_u))
    np.testing.assert_array_equal(np.asarray(free_f), np.asarray(free_u))
    e_end, e_free = ref.mapping_eval_fused_reference(t_proc, sched, chip,
                                                     ppos, chips)
    np.testing.assert_allclose(np.asarray(end_f), e_end, rtol=1e-5)


def test_fused_grid_order_env_and_validation(monkeypatch):
    from repro.kernels import mapping_eval as me

    monkeypatch.delenv("REPRO_FUSED_GRID_ORDER", raising=False)
    assert me.default_grid_order() == "batch_major"
    monkeypatch.setenv("REPRO_FUSED_GRID_ORDER", "pop_major")
    assert me.default_grid_order() == "pop_major"
    monkeypatch.setenv("REPRO_FUSED_GRID_ORDER", "bogus")
    with pytest.raises(ValueError, match="REPRO_FUSED_GRID_ORDER"):
        me.default_grid_order()
    monkeypatch.delenv("REPRO_FUSED_GRID_ORDER", raising=False)
    with pytest.raises(ValueError):
        ops.mapping_eval_fused(jnp.zeros((1, 1, 4)),
                               jnp.zeros((1, 4), jnp.int32),
                               jnp.zeros((1, 4), jnp.int32),
                               jnp.full((1, 4, 1), 4, jnp.int32), 2,
                               grid_order="bogus")


def test_fused_autotune_probe_off_tpu_uses_default(monkeypatch):
    """Off-TPU the probe never times (walltime meaningless interpreted):
    it resolves straight to default_grid_order, honouring the env var."""
    from repro.kernels import mapping_eval as me

    t_proc, sched, chip, ppos = _fused_case(0, 1, 2, rows=2, cols=2,
                                            width=1, chips=2)
    monkeypatch.delenv("REPRO_FUSED_GRID_ORDER", raising=False)
    assert me.autotune_grid_order(jnp.asarray(t_proc), jnp.asarray(sched),
                                  jnp.asarray(chip), jnp.asarray(ppos),
                                  2) == "batch_major"
    monkeypatch.setenv("REPRO_FUSED_GRID_ORDER", "pop_major")
    assert me.autotune_grid_order(jnp.asarray(t_proc), jnp.asarray(sched),
                                  jnp.asarray(chip), jnp.asarray(ppos),
                                  2) == "pop_major"


@pytest.mark.parametrize("fused", [False, True])
def test_mapping_eval_smem_cap_raises(fused):
    """Above the SMEM budget the wrappers raise, naming T and the cap —
    they never hand the schedule to another backend."""
    from repro.kernels import mapping_eval as me

    width, chips = 8, 16
    t_len = 1
    while me.smem_bytes(t_len, width, t_len, chips, fused) \
            <= me.SMEM_BUDGET_BYTES:
        t_len *= 2
    t_proc = jnp.zeros((1, 1, t_len), jnp.float32)
    idx = jnp.zeros((1, t_len), jnp.int32)
    ppos = jnp.full((1, t_len, width), t_len, jnp.int32)
    with pytest.raises(ValueError,
                       match=rf"T={t_len}\b.*{me.SMEM_BUDGET_BYTES}-byte cap"):
        if fused:
            ops.mapping_eval_fused(t_proc, idx, idx, ppos, chips,
                                   grid_order="batch_major", interpret=True)
        else:
            ops.mapping_eval(t_proc, idx, ppos, chips, interpret=True)
