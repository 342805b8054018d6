"""Paper Table V: evaluation-engine validation. Gemini's binary is not
runnable here, so the engine is validated against an independent analytic
accounting of the same layer-pipeline schedule (critical-path latency +
component-wise energy computed directly from the cost tables, bypassing the
engine's scheduler). Error must be < 3% as in the paper."""
import numpy as np

from .common import Timer, emit


def run():
    from repro.core.encoding import pipeline_parallel
    from repro.core.evaluator import CostTables, evaluate
    from repro.core.hardware import (
        DATAFLOWS,
        E_DRAM_PJ_PER_BYTE,
        E_NOP_PJ_PER_BYTE_HOP,
        make_hardware,
    )
    from repro.core.access import data_access_flags
    from repro.core.workload import LLMSpec, build_execution_graph, \
        decode_request, prefill_request

    spec = LLMSpec("gpt3-7b", 4096, 32, 32, 128, 16384, 50257, 32,
                   ffn_gated=False, attn_kind="mha")
    hw = make_hardware(64, "L", tensor_parallel=4)

    with Timer() as t:
        for phase, batch in [
            ("prefill", [prefill_request(512) for _ in range(4)]),
            ("decode", [decode_request(512) for _ in range(128)]),
        ]:
            mb = 4 if phase == "prefill" else 16
            g = build_execution_graph(spec, batch, mb, tp=4, n_blocks=1)
            tables = CostTables.build(g, hw)
            enc = pipeline_parallel(g.rows, g.n_cols, hw.n_chiplets)
            r = evaluate(g, enc, hw, tables)

            # ---- independent accounting (no schedule simulation) ----
            flags = data_access_flags(g, enc, hw)
            flow = np.array([DATAFLOWS.index(f) for f in hw.layout])
            df = flow[enc.layer_to_chip]
            bi, li = np.meshgrid(np.arange(g.rows), np.arange(g.n_cols),
                                 indexing="ij")
            ws_i = DATAFLOWS.index("WS")
            w = tables.weight_bytes[bi, li, df]
            w = np.where(~flags.is_load_wei & (df == ws_i)
                         & tables.ws_resident, 0, w)
            rd = (w + flags.dram_in_bytes * tables.input_reread[bi, li, df]
                  + tables.stream_bytes)
            wr = (np.where(flags.is_write_out,
                           tables.output_bytes[bi, li, df], 0)
                  + tables.psum_bytes[bi, li, df] + tables.extra_write_bytes)
            dram = rd + wr
            hops = np.array([hw.dram_hops(c) for c in range(hw.n_chiplets)])
            e_indep = (tables.comp_energy_pj[bi, li, df].sum()
                       + (dram * E_DRAM_PJ_PER_BYTE).sum()
                       + ((flags.nop_in_byte_hops
                           + dram * hops[enc.layer_to_chip])
                          * E_NOP_PJ_PER_BYTE_HOP).sum()) * 1e-12 * g.scale
            # independent latency: serialised per-chiplet load (upper bound
            # family) and critical path (lower bound) must bracket the engine
            t_proc = np.maximum(tables.comp_seconds[bi, li, df],
                                np.maximum(dram / hw.dram_bw,
                                           flags.nop_in_bytes / hw.nop_bw))
            busy = np.zeros(hw.n_chiplets)
            np.add.at(busy, enc.layer_to_chip.ravel(), t_proc.ravel())
            lower = busy.max() * g.scale
            upper = t_proc.sum() * g.scale

            err_e = abs(r.energy_j - e_indep) / e_indep * 100
            ok_lat = lower <= r.latency_s * (1 + 1e-9) and r.latency_s <= upper
            print(f"# {phase}: engine L={r.latency_s*1e3:.2f}ms "
                  f"(bounds [{lower*1e3:.2f}, {upper*1e3:.2f}]) "
                  f"E={r.energy_j:.3f}J vs indep {e_indep:.3f}J "
                  f"(err {err_e:.2f}%) MC=${r.mc_total:.1f}")
            assert err_e < 3.0, f"energy error {err_e}%"
            assert ok_lat, "latency outside analytic bounds"
    emit("validation_vs_independent", t.us, "energy err < 3%, latency bracketed")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
