"""Whole decode step: the least time the chip needs (every weight read once a step, valid K/V rows, matmul FLOPs) over the decode program's device time, in %. HBM bytes bind it."""

from bench import readers


def read(rec):
    return readers.decode_step_mfu_pct(rec)
