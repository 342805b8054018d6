"""Work counts from shapes, and the chip's peaks.

Both roofline shares of the search count the evaluation's work the same
way whichever timing backend runs it: every input the evaluation must
read once, every output it must write once, and the arithmetic the cost
model and the timing recurrence need. Nothing is counted per scan step or
per kernel launch, so the least time they give is a true lower bound.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

# per (batch, individual, op): the cost pass (weight elision select, DRAM
# read/write sums, two bandwidth divides, the three-way max, DRAM/NoP
# energy and the energy sum) — 16 operations; the recurrence adds one max
# per predecessor slot, one max against the chiplet's free time, one add,
# and the latency reduction one more.
COST_OPS_PER_OP = 16
RECURRENCE_OPS = 3          # + W


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add them with their source")
    return table[device_kind]


def search_eval_work(b: int, p: int, rows: int, m: int, w: int, c: int,
                     d: int = 2) -> tuple:
    """(operations, bytes) of pricing a population of ``p`` mappings of a
    (rows x m)-op graph against ``b`` batches on ``c`` chiplets of ``d``
    dataflows, predecessor window ``w``."""
    t = rows * m
    ops = b * p * t * (COST_OPS_PER_OP + RECURRENCE_OPS + w)
    reads = (p * t * 2 * 4            # scheduled order (row, col) int32
             + p * t * 4              # layer_to_chip int32
             + m * (1 + 2 * w) * 4    # successor counts, predecessor cols
             + c * (c + 2) * 4        # hop matrix, DRAM hops, dataflows
             + b * t * (1 + 4 + 6 * d * 4 + 2 * 4))   # cost tables
    writes = b * p * 2 * 4            # latency and energy
    return float(ops), float(reads + writes)


def least_time_s(ops: float, nbytes: float, pk: dict) -> float:
    return max(ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def lm_layer_params(cfg: dict) -> int:
    """Matrix parameters of one transformer block (biases included)."""
    d, h, kvh, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["head_dim"]
    qkv = d * (h + 2 * kvh) * hd + ((h + 2 * kvh) * hd
                                     if cfg.get("qkv_bias") else 0)
    ffn = (3 if cfg["ffn_gated"] else 2) * d * cfg["d_ff"]
    return qkv + h * hd * d + ffn


def lm_token_flops(cfg: dict, ctx: int) -> float:
    """Forward FLOPs of one token attending ``ctx`` positions: 2 per matrix
    parameter of every block and of the output head, plus QK^T and PV."""
    mats = cfg["n_layers"] * lm_layer_params(cfg) + cfg["d_model"] * \
        cfg["vocab"]
    attn = 4 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * ctx
    return 2.0 * mats + attn


def lm_span_flops(cfg: dict, start: int, n: int) -> float:
    """Forward FLOPs of ``n`` consecutive tokens at positions start.. of one
    sequence (token i attends i + 1 positions)."""
    mats = cfg["n_layers"] * lm_layer_params(cfg) + cfg["d_model"] * \
        cfg["vocab"]
    ctx_sum = n * start + n * (n + 1) // 2
    return 2.0 * mats * n + 4.0 * cfg["n_layers"] * cfg["n_heads"] * \
        cfg["head_dim"] * ctx_sum
