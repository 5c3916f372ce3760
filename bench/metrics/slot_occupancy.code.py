"""Live slots per decode step over the pool size, across the window, in % (the engine's own step and live-slot counters)."""


def read(rec):
    return None if "slot_occupancy" not in rec.counters else 100.0 * rec.counters["slot_occupancy"]
