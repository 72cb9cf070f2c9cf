"""Operations and bytes of the measured layers, from shapes alone, and the
peaks table.  Nothing here looks at how the program implements a layer:
a qdot is counted as the int8 matmul it stands for, so a faster
implementation can only bring its share nearer to 100 %.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str) -> dict:
    """The peaks of a device kind; KeyError for a device not in the
    table (never a default)."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name} "
                       f"({sorted(table)})")
    return table[kind]


def int8_matmul(M: int, K: int, N: int, *, act_bytes: int = 4,
                out_bytes: int = 4, col_tables: int = 4) -> tuple:
    """(ops, bytes) of an int8 (M, K) x (K, N) matmul with the qdot's
    interface: float32 activations in and outputs out, int8 weights, and
    ``col_tables`` float32 per-column tables (weight scale, zero point,
    column sum, compensation column)."""
    ops = 2 * M * K * N
    nbytes = K * N + M * K * act_bytes + M * N * out_bytes + 4 * col_tables * N
    return ops, nbytes


def least_time(ops: float, nbytes: float, pk: dict,
               ops_key: str = "int8_ops_per_s") -> float:
    """The roofline's least time: operations over the peak rate or bytes
    over the memory bandwidth, whichever is longer."""
    return max(ops / pk[ops_key], nbytes / pk["hbm_bytes_per_s"])


def decode_attention_bytes(positions, heads: int, kv_heads: int,
                           head_dim: int, cache_bytes: int = 2) -> int:
    """Bytes one layer's decode attention must move for slots whose
    queries sit at ``positions``: each slot's valid cache rows (K and V,
    positions 0..pos), its new K and V rows written, the float32 query
    read and output written."""
    B = len(positions)
    rows = sum(int(p) + 1 for p in positions)
    cache = rows * kv_heads * head_dim * 2 * cache_bytes
    new = B * kv_heads * head_dim * 2 * cache_bytes
    qo = 2 * B * heads * head_dim * 4
    return cache + new + qo


def projection_macs(cfg: dict) -> int:
    """Multiply-accumulates of one layer's projections for one token."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * (h + 2 * kv) * hd + h * hd * d
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * d * f
    return attn + mlp


def decode_step_ops(cfg: dict, positions) -> int:
    """Operations (2 x MACs) of one decode step over slots at
    ``positions``: every layer's projections and attention over the
    cache (q.k and p.v over pos + 1 rows), and the unembedding."""
    L = cfg["num_hidden_layers"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    B = len(positions)
    attn = sum(2 * h * hd * (int(p) + 1) for p in positions)
    macs = L * (B * projection_macs(cfg) + attn) \
        + B * cfg["hidden_size"] * cfg["vocab_size"]
    return 2 * macs


def prefill_ops(cfg: dict, prompt_len: int) -> int:
    """Operations of one request's prefill: projections and causal
    attention over the prompt, and the unembedding of its last row."""
    L = cfg["num_hidden_layers"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    P = prompt_len
    attn = 2 * h * hd * P * (P + 1) // 2
    macs = L * (P * projection_macs(cfg) + attn) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    return 2 * macs
