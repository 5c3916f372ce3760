"""Decode attention: the least time the chip needs for the work (valid K/V rows of live slots, query, output; bench/flops.py) over the decode-attention kernel's device time, in %."""

from bench import readers


def read(rec):
    return readers.decode_attention_roofline_pct(rec)
