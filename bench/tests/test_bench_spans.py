"""The reductions over the program's own spans and device scopes
(``bench/spans.py``, ``bench/xplane.py``) on a hand-built trace whose answers
are known, and the rule that keeping the program's spans changes nothing the
benchmark's older readers and breakdown read."""

from string import Template

import pytest

from bench import readers, spans, xplane
from bench import trace as tr
from bench.harness import HOST_SPANS, Cell, Record

# Device: the decode program (it runs the decode kernel) over [1000, 3000]
# ns and a prefill over [6000, 7000].  Decode ops: the kernel (500 ns,
# server half), a copy under stack_split (500), the link round (200), a
# merge (300, its scope given by reference), an op with no scope (200) and
# a while container (900, left out).  Host: the traced window [0, 10000],
# the benchmark's spans, and inside them the program's.
DEVICE = Template("""
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 100000 duration_ps: 900000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 1200000 duration_ps: 200000 }
    events { metadata_id: 6 offset_ps: 1400000 duration_ps: 300000 }
    events { metadata_id: 7 offset_ps: 1700000 duration_ps: 200000 }
    events { metadata_id: 8 offset_ps: 5100000 duration_ps: 800000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_traced(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_traced(22)" } }
  event_metadata { key: 3 value { id: 3 name: "%flash_decode_kernel.3 = bf16[2] custom-call()"
    $kernel } }
  event_metadata { key: 4 value { id: 4 name: "%copy.12 = bf16[8] copy()"
    $split } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.7 = f32[8] fusion()"
    $link } }
  event_metadata { key: 6 value { id: 6 name: "%concatenate.2 = bf16[8] concatenate()"
    $merge } }
  event_metadata { key: 7 value { id: 7 name: "%iota.1 = s32[8] iota()" } }
  event_metadata { key: 8 value { id: 8 name: "%slice.4 = bf16[8] slice()"
    $split } }
  event_metadata { key: 10 value { id: 10 name: "%while.4 = (s32[]) while()"
    $loop } }
  stat_metadata { key: 50 value { id: 50 name: "tf_op" } }
  stat_metadata { key: 51 value { id: 51 name: "jit(traced)/vmap(stack_merge)/concatenate:" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
$host }
$host_meta
}
""")
SCOPES = {
    "kernel": 'stats { metadata_id: 50 str_value: "jit(traced)/vmap(di_server_half)/while/body/x:" }',
    "split": 'stats { metadata_id: 50 str_value: "jit(traced)/vmap(stack_split)/slice:" }',
    "link": 'stats { metadata_id: 50 str_value: "jit(traced)/vmap(di_link)/jit(_bernoulli)/mul:" }',
    "merge": "stats { metadata_id: 50 ref_value: 51 }",
    "loop": 'stats { metadata_id: 50 str_value: "jit(traced)/vmap(di_device_half)/while:" }',
}
BENCH_SPANS = [("bench.traced", 0, 10000), ("generator", 200, 900),
               ("engine.step", 3500, 5500), ("take_finished", 5600, 5900)]
PROGRAM_SPANS = [("serve.submit", 300, 800), ("serve.step", 3600, 5400),
                 ("serve.admit", 3700, 4000), ("serve.harvest", 3750, 3900),
                 ("serve.decode", 4100, 4200), ("serve.sync", 4300, 5300),
                 ("serve.harvest", 5650, 5850)]


def _xspace(with_program: bool) -> str:
    host = BENCH_SPANS + (PROGRAM_SPANS if with_program else [])
    names = sorted({n for n, _, _ in host})
    ids = {n: 100 + i for i, n in enumerate(names)}
    events = "\n".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }}"
        for n, s, e in sorted(host, key=lambda h: h[1]))
    meta = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for n, i in ids.items())
    # A program that names no scope still has op_name paths.
    unnamed = 'stats { metadata_id: 50 str_value: "jit(traced)/vmap()/slice:" }'
    scopes = SCOPES if with_program else {k: unnamed for k in SCOPES}
    return DEVICE.substitute(scopes, host=events, host_meta=meta)


def _write(tmp_path, with_program: bool) -> str:
    from jax.profiler import ProfileData

    path = tmp_path / f"{with_program}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_xspace(with_program)))
    return str(path)


def _record(trace) -> Record:
    cell = Cell(root=None, spec={}, workload={}, conf={}, traffic={}, limits={}, seed=0,
                seconds=1.0, trace=True, t_process=0.0)
    return Record(cell=cell, setup_s=0.0, trace=trace, window=tr.window(trace, "bench.traced"))


@pytest.fixture
def with_program(tmp_path):
    return spans.load(_write(tmp_path, True), HOST_SPANS)


@pytest.fixture
def without_program(tmp_path):
    return spans.load(_write(tmp_path, False), HOST_SPANS)


def test_scopes_come_from_event_metadata(with_program):
    trace, scopes = with_program
    scope = {tr.op_name(e.name): scopes["/device:TPU:0"].get(e.name, "") for e in trace.ops[0]}
    assert scope["copy"] == "jit(traced)/vmap(stack_split)/slice:"
    assert scope["concatenate"] == "jit(traced)/vmap(stack_merge)/concatenate:"
    assert scope["iota"] == ""


@pytest.mark.parametrize("path, want", [
    ("jit(traced)/vmap(di_link)/jit(_bernoulli)/mul:", ["traced", "di_link", "_bernoulli", "mul:"]),
    ("jit(traced)/stack_split/slice", ["traced", "stack_split", "slice"]),
    ("vmap(vmap(di_sample))", ["di_sample"]),
])
def test_scope_components_drop_wrappers(path, want):
    assert spans.components(path) == want


READINGS = {
    "split_copy_share": (lambda t, w, s: spans.decode_scope_share_pct(t, w, s, spans.SPLIT_SCOPES),
                         40.0),      # (500 + 300) of 2000 ns
    "link_round_share": (lambda t, w, s: spans.decode_scope_share_pct(t, w, s, spans.LINK_SCOPES),
                         10.0),      # 200 of 2000 ns
    "engine_self_ms": (lambda t, w, s: spans.engine_self_ms(t, w), 650e-6),  # 1800 less 1150 ns
    "idle_in_engine_share": (lambda t, w, s: spans.idle_in_engine_share_pct(t, w),
                             23.0),  # (500 + 1800) of 10000 ns
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_readings_known_values(with_program, without_program, name):
    fn, want = READINGS[name]
    for (trace, scopes), expect in ((with_program, want), (without_program, None)):
        got = fn(trace, tr.window(trace, "bench.traced"), scopes)
        assert got == (pytest.approx(expect) if expect is not None else None)


def test_program_spans_change_no_older_reading(tmp_path, with_program):
    """The program's spans nest inside the benchmark's, which start
    earlier: keeping them changes neither the breakdown nor any older
    reader, on the same trace."""
    a = _record(with_program[0])
    b = _record(tr.load(_write(tmp_path, True), HOST_SPANS))
    assert {e.name for e in a.trace.host} - {e.name for e in b.trace.host} == {
        n for n, _, _ in PROGRAM_SPANS}
    assert a.window == b.window
    assert tr.idle_gaps(a.trace, a.window) == tr.idle_gaps(b.trace, b.window)
    assert [g[0] for g in tr.idle_gaps(a.trace, a.window)] == ["engine.step", "idle", "generator"]
    assert tr.top_ops(a.trace, a.window) == tr.top_ops(b.trace, b.window)
    assert tr.busy_s(a.trace, a.window) == tr.busy_s(b.trace, b.window)
    for fn in (readers.idle_share_pct, readers.decode_step_ms, readers.prefill_share_pct):
        assert fn(a) == fn(b) is not None
    assert readers.mean_span_ms(a, "engine.step") == readers.mean_span_ms(b, "engine.step")


def test_metadata_stat_reads_every_plane():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(_xspace(True))
    got = xplane.metadata_stat(raw, "tf_op")
    assert set(got) == {"/device:TPU:0", "/host:CPU"}
    assert got["/host:CPU"] == {}
    assert len(got["/device:TPU:0"]) == 6
    assert xplane.metadata_stat(raw, "no_such_stat")["/device:TPU:0"] == {}
