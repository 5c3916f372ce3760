"""Operations and bytes that the work needs, from a configuration's shapes.

Counted from what the model must do, whatever kernel does it: a decode
step reads every weight once and the valid K/V rows of its live requests;
decode attention reads those rows, the query and writes the output; a
training step does the forward and backward matmuls (6 per parameter and
token) and the causal attention.  Recomputation, padding and rows that a
kernel reads beyond the valid ones are not counted, so no share of a
roofline or a peak can exceed 100% by an overcount.
"""

from __future__ import annotations

from bench.model import dims


def _itemsize(conf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["torch_dtype"]]


def layer_matmul_params(conf: dict) -> int:
    m = dims(conf)
    q, kv = m["h"] * m["hd"], m["kv"] * m["hd"]
    return m["d"] * (q + 2 * kv) + q * m["d"] + 3 * m["d"] * m["ff"]


def layer_params(conf: dict) -> int:
    m = dims(conf)
    q, kv = m["h"] * m["hd"], m["kv"] * m["hd"]
    return layer_matmul_params(conf) + (q + 2 * kv) + 2 * m["d"]


def param_count(conf: dict) -> int:
    """Every parameter the program holds, the link's two clip vectors
    included."""
    m = dims(conf)
    table = m["vocab"] * m["d"] * (1 if m["tied"] else 2)
    return m["layers"] * layer_params(conf) + table + m["d"] + 2 * m["d"]


def head_params(conf: dict) -> int:
    m = dims(conf)
    return m["vocab"] * m["d"]


def matmul_params(conf: dict) -> int:
    """Parameters that take part in a matmul for every token (the
    embedding lookup is a gather, the head a matmul)."""
    return dims(conf)["layers"] * layer_matmul_params(conf) + head_params(conf)


def attention_flops(conf: dict, rows: float) -> float:
    """Scores and weighted sum of one query against ``rows`` keys, over
    every layer."""
    m = dims(conf)
    return 4.0 * m["h"] * m["hd"] * rows * m["layers"]


def train_step_flops(conf: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 per matmul parameter and token,
    plus causal attention (position i attends to i + 1 keys)."""
    causal_rows = batch * seq * (seq + 1) / 2
    return 6.0 * matmul_params(conf) * batch * seq + 3.0 * attention_flops(conf, causal_rows)


def kv_row_bytes(conf: dict) -> int:
    """One position's K and V in one layer."""
    m = dims(conf)
    return 2 * m["kv"] * m["hd"] * _itemsize(conf)


def kv_read_bytes(conf: dict, valid_rows: float) -> float:
    """K/V bytes a decode step must read for ``valid_rows`` cached
    positions (summed over its live requests), over every layer."""
    return valid_rows * kv_row_bytes(conf) * dims(conf)["layers"]


def weight_read_bytes(conf: dict) -> float:
    """Weights one decode step must read once: every layer, the final norm
    and the head (the tied head is the embedding table; an untied
    embedding is only gathered, a row per request, which is left out)."""
    m = dims(conf)
    return (m["layers"] * layer_params(conf) + head_params(conf) + m["d"]) * _itemsize(conf)


def decode_step(conf: dict, live: float, valid_rows: float) -> tuple:
    """(flops, bytes) one decode step needs for ``live`` requests holding
    ``valid_rows`` cached positions between them."""
    flops = 2.0 * matmul_params(conf) * live + attention_flops(conf, valid_rows)
    return flops, weight_read_bytes(conf) + kv_read_bytes(conf, valid_rows)


def decode_attention(conf: dict, live: float, valid_rows: float) -> tuple:
    """(flops, bytes) of decode attention over every layer: the valid K/V
    rows, the query in and the output out."""
    m = dims(conf)
    qo = 2.0 * live * m["h"] * m["hd"] * _itemsize(conf) * m["layers"]
    return attention_flops(conf, valid_rows), kv_read_bytes(conf, valid_rows) + qo
