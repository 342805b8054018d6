"""Plain reference for pricing one mapping of one serving batch.

A straight transcription of the Compass evaluation model (paper §IV-§V-C)
for dense attention + dense FFN transformer blocks, written for reading
and not for speed: one Python loop per stage, every quantity in float64.
It imports nothing of the program under test and builds its own execution
graph, cost tables, Algorithm-2 access flags and timing recurrence from
the model's sizes, the batch's requests, the package and the mapping.

``rnd`` rounds every intermediate quantity to a lower precision; with
``ml_dtypes.bfloat16`` it is the control of the search cells' comparison
(the same mathematics carried out one precision below the float32 that the
device evaluator states).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache

import numpy as np

FREQ_HZ = 1.0e9
E_MAC_PJ = 0.8
E_GLB_PJ_PER_BYTE = 1.0
E_DRAM_PJ_PER_BYTE = 40.0
E_NOP_PJ_PER_BYTE_HOP = 4.0
E_VECTOR_PJ_PER_OP = 0.4
BYTES_PER_ELEM = 2
RESIDENT_FRACTION = 0.5
STREAM_FRACTION = 0.25
VECTOR_LANES = 256
TILE_GRID = (128, 256, 512, 1024, 2048, 4096, 8192, 16384)
DATAFLOWS = ("WS", "OS")


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@dataclass(frozen=True)
class Package:
    """The multi-chiplet package: uniform chiplets on an (H, W) mesh."""

    macs: int
    glb_bytes: int
    grid: tuple
    layout: tuple
    nop_bw_gbps: float
    dram_bw_gbps: float
    tensor_parallel: int

    @property
    def n_chips(self) -> int:
        return self.grid[0] * self.grid[1]

    def coords(self, c: int):
        return divmod(c, self.grid[1])

    def hops(self, a: int, b: int) -> int:
        (ya, xa), (yb, xb) = self.coords(a), self.coords(b)
        return abs(xa - xb) + abs(ya - yb)

    def dram_hops(self, c: int) -> int:
        _, x = self.coords(c)
        return 1 + min(x, self.grid[1] - 1 - x)


@dataclass(frozen=True)
class Op:
    gemms: tuple          # ((m, k, n, count), ...)
    post_flops: float
    weight_elems: int
    stream_elems: int
    extra_write_elems: int
    out_elems: int
    neutral: bool


def build_graph(model: dict, batch, micro_batch: int, tp: int, n_blocks):
    """Execution graph of one batch: rows = micro-batches, columns = the
    layers of ``n_blocks`` blocks. ``batch`` is a list of (kind, q_len,
    kv_len). Returns (columns [(pred_lo, pred_hi)], ops [rows][M], scale).
    """
    d, h = model["d_model"], model["n_heads"]
    kvh, hd = model["n_kv_heads"], model["head_dim"]
    d_ff, gated = model["d_ff"], model["ffn_gated"]
    n_layers = model["n_layers"]
    n_blocks = 1 if n_blocks is None else min(n_blocks, n_layers)
    m = max(1, min(micro_batch, len(batch)))
    rows = [batch[i:i + m] for i in range(0, len(batch), m)]
    kv_tok = 2 * kvh * hd
    cols, makers = [], []

    def add(lo, hi, make):
        cols.append((lo, hi))
        makers.append(make)
        return len(cols) - 1

    def sq(reqs):
        return sum(q for _, q, _ in reqs)

    prev = -1
    for _ in range(n_blocks):
        c = add(prev, prev + 1 if prev >= 0 else -1, lambda r: Op(
            ((sq(r), d, (h + 2 * kvh) * hd, 1),), 4.0 * sq(r) * d,
            d * (h + 2 * kvh) * hd, 0, 0, sq(r) * (h + 2 * kvh) * hd, False))

        def attn(reqs):
            gemms, post, stream, wr = [], 0.0, 0, 0
            for _, q, kv in reqs:
                gemms += [(q, hd, kv, h), (q, kv, hd, h)]
                post += 5.0 * q * kv * h
                wr += q * kv_tok
                stream += max(0, kv - q) * kv_tok
            return Op(tuple(gemms), post, 0, stream, wr, sq(reqs) * h * hd,
                      True)

        c = add(c, c + 1, attn)
        c = add(c, c + 1, lambda r: Op(
            ((sq(r), h * hd, d, 1),), 4.0 * sq(r) * d, h * hd * d, 0, 0,
            sq(r) * d, False))
        up_n = _cdiv((2 if gated else 1) * d_ff, tp)
        dn_k = _cdiv(d_ff, tp)
        first_up = len(cols)
        for _ in range(tp):
            add(c, c + 1, lambda r: Op(
                ((sq(r), d, up_n, 1),), 2.0 * sq(r) * up_n, d * up_n, 0, 0,
                sq(r) * dn_k, False))
        first_dn = len(cols)
        for i in range(tp):
            add(first_up + i, first_up + i + 1, lambda r: Op(
                ((sq(r), dn_k, d, 1),), 0.0, dn_k * d, 0, 0, sq(r) * d,
                False))
        prev = add(first_dn, first_dn + tp, lambda r: Op(
            (), float(tp * sq(r) * d + 2 * sq(r) * d), 0, 0, 0, sq(r) * d,
            True))
    ops = [[mk(r) for mk in makers] for r in rows]
    return cols, ops, n_layers / n_blocks


@lru_cache(maxsize=1 << 16)
def gemm_cost(m: int, k: int, n: int, macs: int, glb_bytes: int, flow: str):
    """ZigZag-lite cost of (m x k) @ (k x n) on one chiplet under the WS or
    OS template with the capacity-aware tile search. Returns (cycles,
    mac_pj, glb_pj, weight_bytes, output_bytes, reread, ws_resident)."""
    m, k, n = max(1, m), max(1, k), max(1, n)
    a = math.isqrt(macs)
    glb = glb_bytes // BYTES_PER_ELEM
    cap_res, cap_str = int(glb * RESIDENT_FRACTION), int(glb * STREAM_FRACTION)
    kn, mk, mn = float(k) * n, float(m) * k, float(m) * n
    psum = 2.0 * mn * max(0, _cdiv(k, a) - 1)
    best = None
    for tile in TILE_GRID:
        if flow == "WS":
            tk = min(tile, k)
            tn = min(n, max(1, cap_res // tk))
            cn = _cdiv(n, tn)
            mc = min(m, max(1, cap_str // tn))
            w = kn if kn <= cap_res else kn * _cdiv(m, mc)
            rr = 1.0 if mc * k <= cap_str else float(cn)
            g = kn + mk * cn + psum + mn
        else:
            tm = min(tile, m)
            tn = min(n, max(1, cap_res // tm))
            cm, cn = _cdiv(m, tm), _cdiv(n, tn)
            w = kn if kn <= cap_str else kn * cm
            rr = 1.0 if mk <= cap_str else float(cn)
            g = mn + mk * cn + kn * cm + psum
        tot = w + mk * rr + mn
        if best is None or tot < best[0]:
            best = (tot, w, rr, g)
    _, w, rr, g = best
    cycles = _cdiv(k, a) * _cdiv(n, a) * (m + a) if flow == "WS" \
        else _cdiv(m, a) * _cdiv(n, a) * (k + a)
    return (float(cycles), float(m) * k * n * E_MAC_PJ,
            g * BYTES_PER_ELEM * E_GLB_PJ_PER_BYTE, w * BYTES_PER_ELEM,
            mn * BYTES_PER_ELEM, rr, kn <= cap_res)


def op_costs(op: Op, pkg: Package, flow: str):
    """(seconds, energy_pj, weight_bytes, output_bytes, reread) of one op
    on a chiplet of dataflow ``flow``, and whether its weights may stay
    resident on a WS chiplet."""
    out_b = op.out_elems * BYTES_PER_ELEM
    if not op.gemms:
        return (op.post_flops / VECTOR_LANES / FREQ_HZ,
                op.post_flops * E_VECTOR_PJ_PER_OP, 0.0, out_b, 1.0, False)
    eff = "OS" if (op.neutral and flow == "WS") else flow
    cyc = pj = wb = ob = 0.0
    rr, resident, post = 1.0, True, op.post_flops
    for m, k, n, count in op.gemms:
        c = gemm_cost(m, k, n, pkg.macs, pkg.glb_bytes, eff)
        cyc += (c[0] + post / VECTOR_LANES) * count
        pj += (c[1] + post * E_VECTOR_PJ_PER_OP + c[2]) * count
        post = 0.0
        wb += c[3] * count
        ob += c[4] * count
        rr = max(rr, c[5])
        resident = resident and c[6]
    if op.weight_elems == 0:
        wb = 0.0
    return (cyc / FREQ_HZ, pj, wb, min(ob, out_b) if ob else out_b, rr,
            resident and op.weight_elems > 0)


def scheduled_order(segmentation, rows: int, n_cols: int):
    bounds = [0] + [i + 1 for i, s in enumerate(segmentation) if s] \
        + [n_cols]
    return [(b, l) for lo, hi in zip(bounds[:-1], bounds[1:]) if lo < hi
            for b in range(rows) for l in range(lo, hi)]


def identity(x):
    return float(x)


def evaluate(model: dict, batch, micro_batch: int, pkg: Package,
             n_blocks, segmentation, layer_to_chip, rnd=identity):
    """(latency_s, energy_j) of one mapping of one batch."""
    cols, ops, scale = build_graph(model, batch, micro_batch,
                                   pkg.tensor_parallel, n_blocks)
    rows, n_cols = len(ops), len(cols)
    l2c = np.asarray(layer_to_chip)
    assert l2c.shape == (rows, n_cols), (l2c.shape, rows, n_cols)
    order = scheduled_order(segmentation, rows, n_cols)

    # Algorithm 2: weight residency, write-back and activation sourcing
    n_succ = [0] * n_cols
    for lo, hi in cols:
        for p in range(max(lo, 0), hi if lo >= 0 else 0):
            n_succ[p] += 1
    remaining = [list(n_succ) for _ in range(rows)]
    load_w = np.ones((rows, n_cols), bool)
    write_out = np.ones((rows, n_cols), bool)
    nop_in = np.zeros((rows, n_cols))
    nop_hops = np.zeros((rows, n_cols))
    dram_in = np.zeros((rows, n_cols))
    last = {c: (-1, -1) for c in range(pkg.n_chips)}
    for b, l in order:
        chip = int(l2c[b, l])
        if last[chip][1] == l and last[chip][0] != b \
                and ops[b][l].weight_elems > 0:
            load_w[b, l] = False
        lo, hi = cols[l]
        for p in range(lo, hi if lo >= 0 else lo):
            cp = int(l2c[b, p])
            nbytes = ops[b][p].out_elems * BYTES_PER_ELEM
            if last[cp] == (b, p):
                remaining[b][p] -= 1
                if remaining[b][p] == 0:
                    write_out[b, p] = False
                if cp != chip:
                    nop_in[b, l] += nbytes
                    nop_hops[b, l] += nbytes * pkg.hops(cp, chip)
            else:
                dram_in[b, l] += nbytes
        last[chip] = (b, l)

    # per-op time and energy under T_proc = max(T_comp, T_DRAM, T_NoP)
    t_proc = np.zeros((rows, n_cols))
    energy_pj = 0.0
    dram_bw, nop_bw = pkg.dram_bw_gbps * 1e9, pkg.nop_bw_gbps * 1e9
    for b in range(rows):
        for l in range(n_cols):
            op, chip = ops[b][l], int(l2c[b, l])
            flow = pkg.layout[chip]
            sec, pj, wb, ob, rr, resident = (rnd(x) for x in
                                             op_costs(op, pkg, flow))
            if flow == "WS" and not load_w[b, l] and resident:
                wb = 0.0
            wo = ob if write_out[b, l] else 0.0
            dram = rnd(rnd(rnd(wb + rnd(dram_in[b, l] * rr))
                           + op.stream_elems * BYTES_PER_ELEM)
                       + rnd(wo + op.extra_write_elems * BYTES_PER_ELEM))
            t_proc[b, l] = max(sec, rnd(dram / dram_bw),
                               rnd(nop_in[b, l] / nop_bw))
            e = rnd(pj + rnd(dram * E_DRAM_PJ_PER_BYTE))
            e = rnd(e + rnd((nop_hops[b, l] + rnd(dram * pkg.dram_hops(chip)))
                            * E_NOP_PJ_PER_BYTE_HOP))
            energy_pj = rnd(energy_pj + e)

    # timing recurrence: an op starts when its chiplet is free and every
    # predecessor has finished
    end = np.zeros((rows, n_cols))
    free = [0.0] * pkg.n_chips
    for b, l in order:
        chip = int(l2c[b, l])
        lo, hi = cols[l]
        start = free[chip]
        for p in range(lo, hi if lo >= 0 else lo):
            start = max(start, end[b, p])
        end[b, l] = free[chip] = rnd(start + t_proc[b, l])
    return float(end.max()) * scale, energy_pj * 1e-12 * scale
