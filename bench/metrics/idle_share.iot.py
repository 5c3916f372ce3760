"""Share of the traced stretch in which no program ran on the device, in %."""

from bench import readers


def read(rec):
    return readers.idle_share_pct(rec)
