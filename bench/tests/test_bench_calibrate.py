"""``bench/calibrate.py``'s serving seeds at a tiny size on the CPU."""

import time

from bench import calibrate, harness, serve


def test_each_seed_gets_its_own_engine(tiny_root, monkeypatch):
    """The first seed's window ends with requests still in flight (slow
    ticks, no drain); the second seed, on an engine of its own, finishes
    only its own requests and reads its numbers."""
    _, _, conf, traffic, limits = harness.find_cell(tiny_root, "tiny-serve-open")
    engines = []
    real_engine, real_step = serve.engine_for, serve.Window.step

    def engine_for(cfg, pool):
        engines.append(real_engine(cfg, pool))
        return engines[-1]

    def slow_step(self):
        time.sleep(0.02)
        real_step(self)

    monkeypatch.setattr(serve, "engine_for", engine_for)
    with monkeypatch.context() as slow:
        slow.setattr(serve, "DRAIN_S", 0.0)
        slow.setattr(serve.Window, "step", slow_step)
        first = calibrate.serve_seed(conf, traffic, 2**31 + 21, 1.5, limits["served_gap"])
    assert first["failed"] > 0, first           # left in flight at the window's end
    second = calibrate.serve_seed(conf, traffic, 2**31 + 22, 1.5, limits["served_gap"])
    assert second["failed"] == 0 and second["attempted"] > 0, second
    assert len(engines) == 2 and engines[0] is not engines[1]
    for row in (first, second):
        assert row["altered_token_gap"] > limits["served_gap"]["limit"] >= row["served_gap"]
