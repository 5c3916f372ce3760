"""Every cell kind rehearsed end to end at a tiny size on the CPU, and the
harness taking a new cell, mix and metric as data alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from bench import flops, harness, model, serve
from tinycells import CELLS, ROOT, ROUTER, ROUTER_TIE, TINY, TRAFFIC

E2E = {"serve": {"output_tokens_per_s", "request_latency_p95_ms", "setup_s"},
       "train": {"train_tokens_per_s", "setup_s"}}


def run_cell(root, cell, capsys, trace=0, seed=2**31 + 5, seconds=2):
    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                       "--trace", str(trace)],
                      t_process=time.perf_counter(), root=root, require_tpu=False)
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), out.err


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearsal(tiny_root, capsys, cell):
    rc, line, err = run_cell(tiny_root, cell, capsys)
    assert rc == 0 and line["correct"] is True, line
    kind = TRAFFIC[CELLS[cell][1]]["kind"]
    assert set(line["metrics"]) == E2E[kind]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert list(line)[-1] == "check" and set(line["check"]) == set(CELLS[cell][2])
    tail = err.strip().splitlines()[-len(line["check"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_new_cell_mix_and_metric_are_data(tiny_root, capsys):
    """A made-up mix, cell and per-layer metric, added as files and entries
    only, are found by name; a device metric finds nothing on the CPU and
    is left out of the line."""
    bench = tiny_root / "bench"
    mix = dict(TRAFFIC["tiny-open"], arrivals={"process": "poisson", "rate_per_s": 25.0})
    (bench / "traffic" / "made-up-mix.json").write_text(json.dumps(mix))
    (bench / "limits" / "made-up-cell.json").write_text(json.dumps({"served_gap": {"limit": 0.5}}))
    (bench / "metrics" / "made_up_occupancy.py").write_text(
        "def read(rec):\n    return 100.0 * rec.counters['slot_occupancy']\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "made-up-cell", "config": "tiny", "traffic": "made-up-mix",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "made_up_occupancy", "unit": "%", "better": "higher",
                              "source": "program_counter", "layer": "engine",
                              "moves": "output_tokens_per_s", "workloads": ["made-up-cell"]})
    for m in spec["end_to_end"] + spec["per_layer"][:-1]:
        if "workloads" in m and "tiny-serve-open" in m["workloads"]:
            m["workloads"].append("made-up-cell")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, line, _ = run_cell(tiny_root, "made-up-cell", capsys, trace=1)
    assert rc == 0 and line["correct"], line
    assert 0 < line["metrics"]["made_up_occupancy"]["value"] <= 100
    assert "gen_late_p95_ms" in line["metrics"]
    assert "decode_step_ms.code" not in line["metrics"]   # no chip plane on the CPU
    assert line["device"]["window_s"] > 0
    assert "breakdown" in line


def _add_cell(root, conf_name, arch, cell, limit, tie=None, **keys):
    """A configuration naming ``arch`` (with the further ``keys``) and a
    serve cell of it under the open mix (with ``router_tie`` = ``tie``
    where given), as files and entries only."""
    bench = root / "bench"
    conf = dict(TINY, name=conf_name, source="test", architectures=[arch], **keys)
    (bench / "configs" / f"{conf_name}.json").write_text(json.dumps(conf))
    gap = {"limit": limit} if tie is None else {"limit": limit, "router_tie": tie}
    (bench / "limits" / f"{cell}.json").write_text(json.dumps({"served_gap": gap}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": conf_name, "source": "test",
                            "file": f"bench/configs/{conf_name}.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": cell, "config": conf_name, "traffic": "tiny-open",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "tiny-serve-open" in m["workloads"]:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def _bench_files(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file() and "__pycache__" not in p.parts}


# Made-up architectures, each a module under bench/tests, what its
# program configuration must show, and its further configuration keys and
# router-tie settings: Qwen2 with no q, k or v biases; Qwen2 with two
# kinds of layer picked by index (a full-attention prologue, then
# sliding-window layers); and Qwen2 with routed experts, whose cell
# resolves router near-ties.
MADE_UP = {
    "MadeUpNoBiasForCausalLM": ("nobias_arch.py", lambda cfg: cfg.qkv_bias is False, {}, None),
    "MadeUpWindowForCausalLM": ("window_arch.py",
                                lambda cfg: len(cfg.prologue) == 1
                                and cfg.unit_pattern[0].window == 8, {}, None),
    "MadeUpRouterForCausalLM": ("router_arch.py",
                                lambda cfg: cfg.unit_pattern[0].moe
                                and cfg.capacity_factor * cfg.top_k == cfg.num_experts,
                                ROUTER, ROUTER_TIE),
}


@pytest.mark.parametrize("arch", sorted(MADE_UP))
def test_new_architecture_is_files_only(tiny_root, capsys, monkeypatch, arch):
    """A made-up architecture, added as its module, a configuration, a
    cell and its limits, runs a serve cell to ``correct``; no file under
    ``bench/`` is edited, and the float8 control reads above the program
    (and the cell's limit) on it, after any router near-ties are
    resolved."""
    from repro.models import lm

    fixture, shows, keys, tie = MADE_UP[arch]
    before = _bench_files(tiny_root)
    shutil.copy(Path(__file__).with_name(fixture), tiny_root / "bench" / "arch" / f"{arch}.py")
    limit = CELLS["tiny-serve-open"][2]["served_gap"]
    _add_cell(tiny_root, "tiny-made-up", arch, "tiny-made-up-serve", limit, tie, **keys)
    after = _bench_files(tiny_root)
    assert all(after[p] == h for p, h in before.items())

    seen = {}
    real = serve.check_sample

    def with_control(conf, traffic, seed, sample, keys, control=False, gap=None):
        seen.update(real(conf, traffic, seed, sample, keys, control=True, gap=gap))
        return seen

    monkeypatch.setattr(serve, "check_sample", with_control)
    rc, line, _ = run_cell(tiny_root, "tiny-made-up-serve", capsys)
    assert rc == 0 and line["correct"] is True, line
    assert seen["served"] <= limit < seen["control"], seen

    conf = harness.find_cell(tiny_root, "tiny-made-up-serve")[2]
    cfg = model.program_config(conf, {})
    assert shows(cfg), cfg
    shapes = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    assert flops.param_count(conf) == sum(a.size for a in jax.tree_util.tree_leaves(shapes))


def test_unknown_architecture_names_the_path(tiny_root, capsys, monkeypatch):
    """A configuration whose architecture has no module stops the run
    before any weights are drawn, naming the path it looked for."""
    _add_cell(tiny_root, "tiny-nowhere", "NoSuchForCausalLM", "tiny-nowhere-serve", 0.005)

    def no_weights(*a, **k):
        raise AssertionError("weights drawn")

    monkeypatch.setattr(model, "program_params", no_weights)
    with pytest.raises(SystemExit) as e:
        run_cell(tiny_root, "tiny-nowhere-serve", capsys)
    assert str(tiny_root / "bench" / "arch" / "NoSuchForCausalLM.py") in str(e.value)


def test_counters_are_running_totals():
    """The traced window differences two counter readings, so only running
    totals are handed on: the program's derived drop rate is not."""
    class Engine:
        steps, busy_slot_steps = 5, 9

        def device_counters(self):
            return {"decode_steps": 3, "valid_tokens": 40.0, "decode_read_bytes": 8.0,
                    "link_elems": 10.0, "link_dropped": 3.0, "fec_recovered_packets": 1.0,
                    "realized_drop_rate": 0.3}

    assert serve._counters(Engine()) == {
        "decode_steps": 3, "valid_rows": 40.0, "decode_read_bytes": 8.0, "link_elems": 10.0,
        "link_dropped": 3.0, "fec_recovered_packets": 1.0, "engine_steps": 5,
        "live_slot_steps": 9}


def test_no_tpu_no_result(tiny_root, capsys):
    rc, line, err = run_cell_tpu(tiny_root, capsys)
    assert rc != 0 and line is None and "needs a TPU" in err


def run_cell_tpu(root, capsys):
    rc = harness.main(["--workload", "tiny-serve-open", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], t_process=time.perf_counter(), root=root)
    out = capsys.readouterr()
    return rc, (out.out.strip() or None), out.err


def test_checkout_of_benchmark_files_alone_prints_no_result(tmp_path):
    """A directory with BENCHMARK.json and bench/ but not the program:
    the command exits non-zero and prints no result line."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen05b-serve-iot",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
