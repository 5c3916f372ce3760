"""The trace reduction and the table of peaks, on a hand-built trace whose
answers are known and on a trace recorded here on the CPU."""

import glob
import os

import pytest

from bench import peaks, readers
from bench import trace as tr
from bench.harness import Cell, Record

# Device: two programs, the first running the decode kernel; host: the
# traced window [0, 10 us] and one engine.step span over [3.5, 5.5] us.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 5000000 duration_ps: 900000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_traced(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_traced(22)" } }
  event_metadata { key: 3 value { id: 3 name: "%flash_decode_kernel.3 = bf16[2] custom-call()" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.12 = f32[8] fusion()" } }
  event_metadata { key: 5 value { id: 5 name: "%while.4 = (s32[]) while()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 100 duration_ps: 100 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "engine.step" } }
  event_metadata { key: 3 value { id: 3 name: "not.ours" } }
}
"""


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return tr.from_planes(data.planes, ("bench.traced", "engine.step"))


def test_window_and_busy(hand):
    win = tr.window(hand, "bench.traced")
    assert win == (0.0, 10000.0)
    assert [e.name for e in hand.host] == ["bench.traced", "engine.step"]
    # modules [1000, 3000] and [6000, 7000]; the one at 21000 is outside
    assert tr.busy_s(hand, win) == pytest.approx(3000e-9)


def test_programs_and_kernel_time(hand):
    win = tr.window(hand, "bench.traced")
    progs = tr.programs(hand, win)
    assert set(progs) == {"jit_traced(11)", "jit_traced(22)"}
    assert progs["jit_traced(11)"]["count"] == 1
    assert progs["jit_traced(11)"]["seconds"] == pytest.approx(2000e-9)
    assert tr.runs_op(progs["jit_traced(11)"], "flash_decode_kernel")
    assert not tr.runs_op(progs["jit_traced(22)"], "flash_decode_kernel")
    assert tr.op_seconds(hand, win, "flash_decode_kernel") == (1, pytest.approx(500e-9))


def test_top_ops_leave_out_containers(hand):
    win = tr.window(hand, "bench.traced")
    top = tr.top_ops(hand, win)
    assert top == [["fusion", pytest.approx(1000e-9)],
                   ["flash_decode_kernel", pytest.approx(500e-9)]]


def test_idle_gaps_named_by_host_span(hand):
    win = tr.window(hand, "bench.traced")
    gaps = tr.idle_gaps(hand, win)
    assert gaps[0] == ["engine.step", pytest.approx(3000e-9)]
    assert gaps[1] == ["idle", pytest.approx(3000e-9)]
    assert gaps[2] == ["idle", pytest.approx(1000e-9)]


@pytest.mark.parametrize("ivs, want", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(-5, 1), (9, 20)], [(0, 1), (9, 10)]),
    ([(2, 2), (4, 3)], []),
])
def test_union_clips_and_merges(ivs, want):
    assert tr.union(ivs, 0, 10) == want


def test_op_names_are_stable():
    assert tr.op_name("%flash_decode_kernel.13 = bf16[64] custom-call(x)") == "flash_decode_kernel"
    assert tr.op_name("%copy-start.16 = (f32[1]) copy-start(y)") == "copy-start"
    assert tr.module_name("jit_traced(15681670333429283227)") == "jit_traced"


def test_readers_on_hand_trace(hand):
    cell = Cell(root=None, spec={}, workload={}, conf={}, traffic={}, limits={}, seed=0,
                seconds=1.0, trace=True, t_process=0.0)
    rec = Record(cell=cell, setup_s=0.0, trace=hand, window=tr.window(hand, "bench.traced"))
    assert readers.idle_share_pct(rec) == pytest.approx(70.0)
    assert readers.decode_step_ms(rec) == pytest.approx(2000e-6)
    assert readers.prefill_share_pct(rec) == pytest.approx(100.0 / 3)
    assert readers.mean_span_ms(rec, "engine.step") == pytest.approx(2000e-6)


def test_readers_find_nothing_without_a_trace():
    cell = Cell(root=None, spec={}, workload={}, conf={}, traffic={}, limits={}, seed=0,
                seconds=1.0, trace=False, t_process=0.0)
    rec = Record(cell=cell, setup_s=0.0)
    for fn in (readers.idle_share_pct, readers.decode_step_ms, readers.prefill_share_pct,
               readers.decode_attention_roofline_pct, readers.decode_step_mfu_pct,
               readers.train_mfu_pct):
        assert fn(rec) is None


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here: the host spans are found, no chip plane is,
    and the device readers then find nothing (no share reads 0)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("engine.step"):
                    f(x).block_until_ready()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)[0]
    t = tr.load(path, ("bench.traced", "engine.step"))
    win = tr.window(t, "bench.traced")
    assert len(tr.host_span_seconds(t, win, "engine.step")) == 3
    assert t.modules == {}
    cell = Cell(root=None, spec={}, workload={}, conf={}, traffic={}, limits={}, seed=0,
                seconds=1.0, trace=True, t_process=0.0)
    rec = Record(cell=cell, setup_s=0.0, trace=t, window=win)
    assert readers.idle_share_pct(rec) is None
    assert readers.decode_step_ms(rec) is None


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    assert peaks.bound_seconds(197e12, 0.0, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.bound_seconds(0.0, 819e9 * 2, "TPU v5 lite") == pytest.approx(2.0)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("first_poll", [1.0, 4.5])
def test_trace_runs_its_length_from_its_own_start(monkeypatch, first_poll):
    """Under a fake clock: the trace starts at the first poll at or after
    ``at`` and runs ``dur`` from there, also where one long tick put that
    poll past ``at + dur``; the window's end closes it in any case."""
    import jax

    from bench import harness

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    reads = iter(range(100))
    traced = harness._Traced(None, lambda: {"n": next(reads)}, at=1.0, dur=3.0)
    traced.poll(0.5)
    assert traced.state == "before"
    traced.poll(first_poll)
    assert traced.state == "on"
    traced.poll(first_poll + 2.99)
    assert traced.state == "on"
    traced.poll(first_poll + 3.0)
    assert traced.state == "done" and traced.counters == {"n": 1}

    cut = harness._Traced(None, lambda: {"n": 0}, at=1.0, dur=3.0)
    cut.poll(first_poll)
    cut.close()                      # the window ends first
    assert cut.state == "done"
