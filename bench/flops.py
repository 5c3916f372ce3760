"""Operations and bytes that the work needs, from a configuration's shapes.

Counted from what the model must do, whatever kernel does it: a decode
step reads every weight it uses once and the valid cache rows of its live
requests; decode attention reads those rows, the query and writes the
output; a training step does the forward and backward matmuls (6 per
parameter and token) and the causal attention.  Recomputation, padding
and rows that a kernel reads beyond the valid ones are not counted, so no
share of a roofline or a peak can exceed 100% by an overcount.

The counts depend on the architecture.  Its module (``bench/arch``) gives
the four that the benchmark reads: ``param_count``, ``train_step_flops``,
``decode_steps`` and ``decode_attention``.  The other counts here are
parts of those that a module may give, as Qwen2's does; for one that does
not, they fail.
"""

from __future__ import annotations

from bench.model import arch


def _part(conf: dict, name: str):
    fn = getattr(arch(conf), name, None)
    if fn is None:
        raise NotImplementedError(f"architecture {conf['architectures'][0]!r} gives no {name}")
    return fn


def itemsize(conf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["torch_dtype"]]


def layer_matmul_params(conf: dict) -> int:
    return _part(conf, "layer_matmul_params")(conf)


def layer_params(conf: dict) -> int:
    return _part(conf, "layer_params")(conf)


def param_count(conf: dict) -> int:
    """Every parameter the program holds, the link's two clip vectors
    included."""
    return arch(conf).param_count(conf)


def head_params(conf: dict) -> int:
    return _part(conf, "head_params")(conf)


def matmul_params(conf: dict) -> int:
    """Parameters that take part in a matmul for every token (the
    embedding lookup is a gather, the head a matmul)."""
    return _part(conf, "matmul_params")(conf)


def attention_flops(conf: dict, rows: float) -> float:
    """Scores and weighted sum of one query against ``rows`` keys, over
    every layer."""
    return _part(conf, "attention_flops")(conf, rows)


def train_step_flops(conf: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 per matmul parameter and token,
    plus causal attention (position i attends to i + 1 keys)."""
    return arch(conf).train_step_flops(conf, batch, seq)


def kv_row_bytes(conf: dict) -> int:
    """One position's cache row (K and V, or whatever the architecture
    caches) in one layer."""
    return _part(conf, "kv_row_bytes")(conf)


def kv_read_bytes(conf: dict, valid_rows: float) -> float:
    """Cache bytes a decode step must read for ``valid_rows`` cached
    positions (summed over its live requests), over every layer."""
    return _part(conf, "kv_read_bytes")(conf, valid_rows)


def weight_read_bytes(conf: dict) -> float:
    """Weights one decode step must read once: every layer, the final norm
    and the head (the tied head is the embedding table; an untied
    embedding is only gathered, a row per request, which is left out)."""
    return _part(conf, "weight_read_bytes")(conf)


def decode_step(conf: dict, live: float, valid_rows: float) -> tuple:
    """(flops, bytes) one decode step needs for ``live`` requests holding
    ``valid_rows`` cached positions between them."""
    return decode_steps(conf, 1, {"live_slot_steps": live, "valid_rows": valid_rows})


def decode_steps(conf: dict, steps: int, counters: dict) -> tuple:
    """(flops, bytes) ``steps`` decode steps need, from the program's
    counters over them: ``live_slot_steps`` and ``valid_rows`` summed over
    the steps, and whatever else the architecture's bytes depend on (which
    experts a step touched, say)."""
    return arch(conf).decode_steps(conf, steps, counters)


def decode_attention(conf: dict, live: float, valid_rows: float) -> tuple:
    """(flops, bytes) of decode attention over every layer: the valid cache
    rows, the query in and the output out."""
    return arch(conf).decode_attention(conf, live, valid_rows)
