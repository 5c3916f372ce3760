"""How late the load generator sent each request after its due time, 95th
percentile over the window before the profiler starts (its first 30%), in
ms (host clock)."""


def read(rec):
    return rec.counters.get("gen_late_p95_ms")
