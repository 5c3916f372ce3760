"""Every cell kind rehearsed end to end at a tiny size on the CPU, and the
harness taking a new cell, mix and metric as data alone."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from tinycells import CELLS, ROOT, TRAFFIC

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
