"""Device time in the engine's prefill programs over device busy time in the traced stretch, in %."""

from bench import readers


def read(rec):
    return readers.prefill_share_pct(rec)
