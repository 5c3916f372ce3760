"""Tiny cells of the same kinds as the benchmark's, for CPU tests:
``build_root`` lays out a checkout-like directory whose ``BENCHMARK.json``
names them, with the real traffic shapes scaled down."""

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "architectures": ["Qwen2ForCausalLM"], "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 512, "hidden_act": "silu", "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "reduced": [],
    "link": {"split_after_layers": 1, "compression": "quant", "quant_bits": 8,
             "clip": [-6.0, 6.0], "elements_per_packet": 25, "shuffle": True,
             "dropout_rate": 0.2},
}
# A routed-expert configuration's further keys (``router_arch.py``), and
# its cells' router-tie settings: at these widths a bfloat16 router moves
# the margin between the k-th and the (k+1)-th probability by 0.0035 at
# most over 4096 random positions (0.0017 at the 99th percentile); the
# tie margin is twice that.
ROUTER = {"num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32}
ROUTER_TIE = {"tie_margin": 0.007, "max_flips": 2,
              "from": "CPU: twice the largest bf16 shift of the margin at these widths"}
TRAFFIC = {
    "tiny-open": {
        "kind": "serve", "arrivals": {"process": "poisson", "rate_per_s": 40.0},
        "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.7, "min": 3, "max": 32},
        "output_len": {"dist": "lognormal", "median": 5, "sigma": 0.6, "min": 2, "max": 12},
        "link": {"channel": "ge", "loss_rate": 0.3, "channel_params": {"burst_len": 4.0}},
        "pool": {"max_slots": 8, "max_prompt": 32, "max_new": 16},
        "check": {"max_requests": 4, "min_tokens": 20}},
    "tiny-closed": {
        "kind": "serve",
        "arrivals": {"process": "closed", "clients": 4, "requests_per_client": 16, "ramp_s": 0.2},
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 4, "max": 32},
        "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.8, "min": 2, "max": 16},
        "link": {"channel": "ge", "loss_rate": 0.3, "channel_params": {"burst_len": 4.0}},
        "pool": {"max_slots": 4, "max_prompt": 32, "max_new": 16},
        "check": {"max_requests": 3, "min_tokens": 20}},
    "tiny-train": {
        "kind": "train", "batch": 4, "seq": 16, "steps_per_dispatch": 3, "remat": True,
        "link": {"train_link": "dropout"},
        "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "clip_norm": 1.0,
                      "state_dtype": "float32"}},
}
CELLS = {
    # Limits from CPU readings at these sizes (six seeds each): served gap
    # 0 to 0.001 open, 0 to 0.015 closed; the float8 control 0.002-0.02
    # open, 0-0.18 closed.  Training: the program reads loss 0.0005-0.0014,
    # moment 0.007-0.018, change 0.010-0.018, first moment's difference
    # 0.042-0.057; the control (e4m3, e5m2 cotangents) loss 0.0026-0.0058,
    # difference 0.20-0.24; half the batch loss 0.006-0.039.
    "tiny-serve-open": ("tiny", "tiny-open", {"served_gap": 0.005}),
    "tiny-serve-closed": ("tiny-untied", "tiny-closed", {"served_gap": 0.03}),
    "tiny-train": ("tiny", "tiny-train",
                   {"loss_gap": 0.002, "moment_gap": 0.06, "change_gap": 0.06,
                    "moment_diff": 0.12}),
}


def build_root(path: Path) -> Path:
    """A directory laid out like a checkout: BENCHMARK.json naming the
    tiny cells, their data files, and the real metric readers and
    architecture modules."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    (path / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "limits"):
        (path / "bench" / sub).mkdir()
    for sub in ("metrics", "arch"):
        shutil.copytree(ROOT / "bench" / sub, path / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    configs = []
    for name, tied in (("tiny", True), ("tiny-untied", False)):
        conf = dict(TINY, name=name, source="test", tie_word_embeddings=tied)
        (path / "bench" / "configs" / f"{name}.json").write_text(json.dumps(conf))
        configs.append({"name": name, "source": "test", "file": f"bench/configs/{name}.json",
                        "reduced": [], "why": "test"})
    for name, traffic in TRAFFIC.items():
        (path / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    workloads = []
    for cell, (conf, traffic, limits) in CELLS.items():
        workloads.append({"name": cell, "config": conf, "traffic": traffic,
                          "chips": 1, "why": "test"})
        (path / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps({k: {"limit": v} for k, v in limits.items()}))
    e2e = [dict(m) for m in real["end_to_end"]]
    if not any(m["name"] == "train_tokens_per_s" for m in e2e):
        # The tiny training cell reports the training rate the harness measures.
        e2e.append({"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
                    "bound": 0.01, "source": "host_clock", "workloads": []})
    for m in e2e:
        if "workloads" in m:
            m["workloads"] = [c for c, (_, t, _) in CELLS.items()
                              if TRAFFIC[t]["kind"] == ("train" if m["name"].startswith("train")
                                                        else "serve")]
    spec = dict(real, configs=configs, workloads=workloads, end_to_end=e2e,
                per_layer=[dict(m, workloads=list(CELLS)) for m in real["per_layer"]])
    (path / "BENCHMARK.json").write_text(json.dumps(spec))
    return path
