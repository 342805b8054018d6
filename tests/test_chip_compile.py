"""Ahead-of-time compiles of the mapping-eval kernels for a described TPU
v5e chip. Nothing runs: the TPU compiler, which is installed without a
chip, must accept both kernels and both grid orders of the fused one at the
schedule lengths ``chip_smoke.py`` searches, at T=4096, and at the T cap
that the kernels' SMEM budget admits. Interpret mode cannot see what these
catch: block shapes that break the TPU tiling rule, dynamic lane-axis
accesses Mosaic refuses, and SMEM overflow.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import mapping_eval as me

ROOT = Path(__file__).resolve().parents[1]

# the smoke's search: gpt3-7b, 32 blocks, 2..4 micro-batch rows of 640
# columns, TP=8 fan-in (W=8), 16 chiplets
SMOKE_T = (1280, 1920, 2560)
SMOKE_W = 8
N_CHIPS = 16
POP, N_BATCH = 32, 3
KERNELS = (("mapping_eval", None), ("mapping_eval_fused", "batch_major"),
           ("mapping_eval_fused", "pop_major"))


def _cap_t(width: int) -> int:
    """Largest T whose fused call fits :data:`me.SMEM_BUDGET_BYTES`."""
    t = 1
    while me.smem_bytes(t + 1, width, t + 1, N_CHIPS, True) \
            <= me.SMEM_BUDGET_BYTES:
        t += 1
    return t


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(one_chip, kernel, grid_order, t_len, width):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tproc = sds((N_BATCH, POP, t_len), jnp.float32)
    idx = sds((POP, t_len), jnp.int32)
    ppos = sds((POP, t_len, width), jnp.int32)
    if kernel == "mapping_eval":
        fn = jax.jit(lambda tp, c, pp: me.mapping_eval(tp, c, pp, N_CHIPS))
        return fn.lower(tproc, idx, ppos).compile()
    fn = jax.jit(lambda tp, s, c, pp: me.mapping_eval_fused(
        tp, s, c, pp, N_CHIPS, grid_order=grid_order))
    return fn.lower(tproc, idx, idx, ppos).compile()


@pytest.mark.parametrize("t_len", SMOKE_T + (4096,))
@pytest.mark.parametrize("kernel,grid_order", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kernel,
                                 grid_order, t_len):
    compiled = _compile(one_chip, kernel, grid_order, t_len, SMOKE_W)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid_order", me.GRID_ORDERS)
def test_fused_kernel_compiles_at_smem_cap(one_chip, no_persistent_cache,
                                           grid_order):
    """The SMEM budget is honest: the longest schedule it admits compiles,
    one step longer is refused by the wrapper with T and the cap named."""
    cap = _cap_t(SMOKE_W)
    assert cap >= 4096
    compiled = _compile(one_chip, "mapping_eval_fused", grid_order, cap,
                        SMOKE_W)
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(ValueError, match=rf"T={cap + 1}\b.*cap"):
        _compile(one_chip, "mapping_eval_fused", grid_order, cap + 1,
                 SMOKE_W)


def test_smoke_scenario_shapes_are_the_compiled_ones():
    """The (T, W, C) the smoke searches are the ones compiled above."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    from repro.core.timing import (get_graph_and_tables,
                                   padded_predecessor_columns)

    sc, hw, ro = chip_smoke.search_setup()
    assert hw.n_chiplets == N_CHIPS
    lens, widths = set(), set()
    for b in ro.batches:
        g, _ = get_graph_and_tables(sc.spec, b, hw, sc.micro_batch(hw, b),
                                    sc.n_blocks)
        lens.add(g.rows * g.n_cols)
        cols, _ = padded_predecessor_columns(
            [m.pred_lo for m in g.layers], [m.pred_hi for m in g.layers])
        widths.add(cols.shape[1])
    assert lens == set(SMOKE_T)
    assert widths == {SMOKE_W}


def test_paged_decode_entry_compiles_at_backlog_shapes(one_chip,
                                                      no_persistent_cache,
                                                      monkeypatch):
    """The served decode entry at the backlog cell's shapes (qwen1.5-0.5b
    widths, float32, 16 requests, 2,049 blocks of 16, max_len 2,048)
    compiles for the chip with the paged kernel in it, and needs less
    scratch than the 0.58e9 B the gathered dense view took."""
    import json
    from functools import partial

    from repro.kernels import ops
    from repro.models import init_model
    from repro.models.paged import init_paged_pools, paged_decode
    from repro.models.transformer import ModelConfig

    cell = json.loads((ROOT / "bench" / "configs" / "qwen1.5-0.5b.json")
                      .read_text())
    cfg = ModelConfig(name=cell["name"], **cell["model"])
    sv = cell["service"]
    b, bl = sv["max_batch"], sv["block_len"]
    t = sv["max_len"] // bl
    n_blocks = b * t + 1

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        partial(init_model, cfg=cfg), jax.random.PRNGKey(0)))
    pools = jax.tree.map(sds, jax.eval_shape(
        lambda: init_paged_pools(cfg, b, n_blocks, bl, jnp.float32)))
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    # this process's backend is the CPU: lower the kernel for the chip
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    fn = jax.jit(partial(paged_decode, cfg=cfg, block_len=bl))
    with jax.default_matmul_precision(cell["matmul_precision"]):
        compiled = fn.lower(params, tokens=i32((b,)), pools=pools,
                            tables=i32((b, t)), lens=i32((b,)),
                            slots=i32((b,))).compile()
    assert compiled.as_text().count("tpu_custom_call") >= cfg.n_layers
    assert compiled.memory_analysis().temp_size_in_bytes < 0.58e9
