"""Host wall time per engine.step() call in the traced stretch, in ms: admission, prefill and decode dispatch, per-slot bookkeeping and the completion syncs inside it (the benchmark's own spans)."""

from bench import readers


def read(rec):
    return readers.mean_span_ms(rec, "engine.step")
