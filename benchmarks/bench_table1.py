"""Paper Table I: EDP ratio (OS/WS) of GPT3-7B GEMMs across phases and
sequence lengths. Reproduces the structure (rows = lengths, cols = phases);
our ZigZag-lite model reproduces the qualitative preference pattern
(WS for weight-dominated short/decode GEMMs, OS for long-sequence merged
GEMMs) — absolute ratios differ from the paper's ZigZag config, and
activation-activation GEMMs (QK^T) are dataflow-neutral in our model
(DESIGN.md §6)."""
from .common import Timer, emit


def gemm_edp(m, k, n, flow, spec, reuse_passes=1):
    from repro.core.dataflow import gemm_cost
    from repro.core.hardware import (
        E_DRAM_PJ_PER_BYTE,
        )

    c = gemm_cost(m, k, n, spec, flow)
    w = c.weight_bytes
    if flow == "WS" and c.ws_resident_ok and reuse_passes > 1:
        w = w / reuse_passes  # cross-micro-batch residency (Algorithm 2)
    dram = w + c.input_bytes + c.output_bytes + c.psum_spill_bytes
    lat = max(c.compute_seconds, dram / 16e9)
    en = (c.mac_energy_pj + c.glb_energy_pj + dram * E_DRAM_PJ_PER_BYTE) * 1e-12
    return lat * en


def run():
    from repro.core.hardware import CHIPLET_LIBRARY

    spec = CHIPLET_LIBRARY["L"]
    d, dff, h, hd = 4096, 16384, 32, 128
    phases = {
        "QKVGen": lambda L: (L, d, 3 * d),
        "QK^T": lambda L: (L, hd, L),
        "FFN1": lambda L: (L, d, dff),
        "FFN2": lambda L: (L, dff, d),
    }
    print("# Table I reproduction: EDP ratio OS/WS (>1 -> WS superior)")
    print("lens," + ",".join(phases))
    with Timer() as t:
        for L in (128, 1024, 5120, 10240):
            row = [str(L)]
            for _name, dims in phases.items():
                m, k, n = dims(L)
                # short sequences come with many micro-batches in serving
                reuse = max(1, 2048 // max(L, 1))
                ws = gemm_edp(m, k, n, "WS", spec, reuse_passes=reuse)
                os_ = gemm_edp(m, k, n, "OS", spec)
                row.append(f"{os_ / ws:.2f}")
            print(",".join(row))
        # decode row (GEMV with batch merging, deep reuse)
        row = ["decode(b128)"]
        for _name, dims in phases.items():
            m, k, n = dims(128)
            ws = gemm_edp(128, k, n, "WS", spec, reuse_passes=8)
            os_ = gemm_edp(128, k, n, "OS", spec)
            row.append(f"{os_ / ws:.2f}")
        print(",".join(row))
    emit("table1_os_ws_ratio", t.us, "see rows above")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
