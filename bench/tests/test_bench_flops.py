"""Operations and bytes against the program's own counts, and the seeded
weights against the program's parameter layout and the reference's."""

import hashlib

import jax
import numpy as np
import pytest

from bench import flops, model, reference
from tinycells import ROOT, TINY

CONFIGS = ["qwen1.5-0.5b", "codeqwen1.5-7b"]

# Counts, seeded weights and reference outputs of parent commit f6eec09
# (before the architecture's part moved to bench/arch), computed there
# with these arguments; ``decode_steps`` is that commit's arithmetic in
# ``readers.decode_step_mfu_pct``: ``decode_step`` plus the weights read
# again by each further step.
LIVE, VALID, BATCH, SEQ, STEPS = 64, 64 * 320, 8, 512, 10
PARENT_COUNTS = {
    "qwen1.5-0.5b": {
        "layer_matmul_params": 12845056, "layer_params": 12850176,
        "param_count": 463989760, "head_params": 155582464, "matmul_params": 463863808,
        "attention_flops": 2013265920.0, "train_step_flops": 11709758570496.0,
        "kv_row_bytes": 4096, "kv_read_bytes": 2013265920, "weight_read_bytes": 927975424,
        "decode_step": (61387833344.0, 2941241344),
        "decode_attention": (2013265920.0, 2019557376.0),
        "decode_steps": (61387833344.0, 11293020160),
    },
    "codeqwen1.5-7b": {
        "layer_matmul_params": 202899456, "layer_params": 202912768,
        "param_count": 4003688448, "head_params": 378535936, "matmul_params": 3624927232,
        "attention_flops": 5368709120.0, "train_step_flops": 89912455987200.0,
        "kv_row_bytes": 2048, "kv_read_bytes": 671088640, "weight_read_bytes": 7250288640,
        "decode_step": (469359394816.0, 7921377280),
        "decode_attention": (5368709120.0, 687865856.0),
        "decode_steps": (469359394816.0, 73173975040),
    },
}
# sha256 over the leaves (path, dtype, shape, bytes), seed 2**31 + 12345.
PARENT_PARAMS = {
    "tiny": "0b7ba0bd4532bfc03b147ffb40b679c76f3c5bdbc27f392add75f1c721da6031",
    "tiny-untied": "08a8edebdd2eea2f46f23306f67cc098b67040f45eabe602614c4313175793f1",
}
PARENT_REFERENCE = {
    "tiny": "93b17de1f6ecc97ceea9d56669a6706b936d343761cc169f95186d9dda0be948",
    "tiny-untied": "f0f183719f659ab1cbc48b0ab2f26b166d55ac8f28cab3f6d576b1d75557833c",
}
PIN_SEED = 2**31 + 12345


def _conf(name):
    return model.read_conf(ROOT, f"bench/configs/{name}.json")


def _tiny(name):
    return dict(TINY, name=name, source="test", tie_word_embeddings=name == "tiny",
                bench_root=str(ROOT))


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda p: jax.tree_util.keystr(p[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_equal_the_parents(name):
    conf = _conf(name)
    got = {
        "layer_matmul_params": flops.layer_matmul_params(conf),
        "layer_params": flops.layer_params(conf),
        "param_count": flops.param_count(conf),
        "head_params": flops.head_params(conf),
        "matmul_params": flops.matmul_params(conf),
        "attention_flops": flops.attention_flops(conf, VALID),
        "train_step_flops": flops.train_step_flops(conf, BATCH, SEQ),
        "kv_row_bytes": flops.kv_row_bytes(conf),
        "kv_read_bytes": flops.kv_read_bytes(conf, VALID),
        "weight_read_bytes": flops.weight_read_bytes(conf),
        "decode_step": flops.decode_step(conf, LIVE, VALID),
        "decode_attention": flops.decode_attention(conf, LIVE, VALID),
        "decode_steps": flops.decode_steps(
            conf, STEPS, {"live_slot_steps": LIVE, "valid_rows": VALID}),
    }
    assert got == PARENT_COUNTS[name]


@pytest.mark.parametrize("name", sorted(PARENT_PARAMS))
def test_seeded_params_equal_the_parents(name):
    assert _digest(model.program_params(_tiny(name), PIN_SEED)) == PARENT_PARAMS[name]


@pytest.mark.parametrize("name", sorted(PARENT_REFERENCE))
def test_reference_outputs_equal_the_parents(name):
    """Final hidden states and logits of the float32 reference on a fixed
    token batch with a fixed keep-mask at the split."""
    conf = _tiny(name)
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, conf["vocab_size"], (3, 12), dtype=np.int32)
    keep = (rng.random((3, 12, conf["hidden_size"])) >= 0.3).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        x, outer = reference.serve_hidden(conf, PIN_SEED, tokens, jax.numpy.asarray(keep),
                                          0.3, "f32")
        logits = reference.head_logits(x, outer, conf, "f32")
    assert _digest({"hidden": x, "logits": logits}) == PARENT_REFERENCE[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program(name):
    from repro.models import lm

    conf = _conf(name)
    cfg = model.program_config(conf, {"channel": "ge", "loss_rate": 0.3})
    shapes = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), cfg))
    want = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert flops.param_count(conf) == want


def test_published_sizes():
    """About 0.46 B parameters for the 0.5B model; the 7B's layers at 4 KV
    heads hold 202.9 M each (its published size with 32 layers, 7.25 B)."""
    small, code = _conf("qwen1.5-0.5b"), _conf("codeqwen1.5-7b")
    assert 0.45e9 < flops.param_count(small) < 0.47e9
    assert flops.layer_params(code) == pytest.approx(202.9e6, rel=1e-3)
    full = dict(code, num_hidden_layers=32)
    assert flops.param_count(full) == pytest.approx(7.25e9, rel=0.01)


@pytest.mark.parametrize("valid", [16, 48, 64])
def test_kv_bytes_match_paged_read_bytes(valid):
    """The valid K/V bytes equal what the program's own analytic says the
    paged kernel reads at a whole number of blocks, less its block-table
    and length metadata (4 bytes a table entry, plus 4)."""
    from repro.models import cache

    conf = _tiny("tiny")
    cfg = model.program_config(conf, {})
    max_seq, bs = 64, 16
    got = cache.decode_read_bytes(cfg, max_seq, valid, paged=True, block_size=bs)
    meta = conf["num_hidden_layers"] * (4 * cache.blocks_for(max_seq, bs) + 4)
    assert flops.kv_read_bytes(conf, valid) == got - meta


def test_decode_step_and_train_counts():
    conf = _conf("qwen1.5-0.5b")
    f, b = flops.decode_step(conf, live=8, valid_rows=8 * 100)
    assert b == flops.weight_read_bytes(conf) + flops.kv_read_bytes(conf, 800)
    assert f == 2 * flops.matmul_params(conf) * 8 + flops.attention_flops(conf, 800)
    # the tied head reads the whole table; weights ~0.93 GB in bf16
    assert 0.9e9 < flops.weight_read_bytes(conf) < 0.95e9
    step = flops.train_step_flops(conf, 8, 512)
    assert 6 * flops.matmul_params(conf) * 4096 < step < 1.2 * 6 * flops.matmul_params(conf) * 4096
    fa, ba = flops.decode_attention(conf, live=2, valid_rows=10)
    assert fa == 4 * 16 * 64 * 10 * 24
    assert ba == 10 * 2 * 16 * 64 * 2 * 24 + 2 * 2 * 16 * 64 * 2 * 24


def test_weights_have_program_layout_and_reference_values():
    """One jitted call makes the program's tree; the reference draws any
    layer again from the seed and gets the same served values."""
    conf = _tiny("tiny-untied")
    cfg = model.program_config(conf, {})
    seed = 2**31 + 12345
    params = model.program_params(conf, seed)
    model.check_layout(params, cfg)
    unit = params["stack"]["units"][0]
    for i in range(conf["num_hidden_layers"]):
        ref = reference.layer_at(conf, seed, i)
        np.testing.assert_array_equal(np.asarray(unit["mix"]["wq"][i], np.float32), ref["wq"])
        np.testing.assert_array_equal(np.asarray(unit["ffn"]["w_down"][i], np.float32),
                                      ref["w_down"])
        np.testing.assert_array_equal(np.asarray(unit["norm1"]["scale"][i], np.float32),
                                      ref["ln1"])
    outer = reference.outer_at(conf, seed)
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32), outer["lm_head"])
    other = model.program_params(conf, seed + 1)
    assert not np.array_equal(np.asarray(other["embed"]), np.asarray(params["embed"]))


def test_reference_link_mask_matches_program():
    """The reference's Gilbert–Elliott mask, drawn from a key on its own,
    equals the program's channel for that key."""
    from repro.net.channels import make_channel

    ge = reference.ge_params(0.3, burst_len=4.0)
    ch = make_channel("ge", loss_rate=0.3, burst_len=4.0)
    assert (ch.p_gb, ch.p_bg) == pytest.approx(ge[:2])
    assert ch.stationary_loss_rate == pytest.approx(ge[-1])
    for k in range(3):
        key = jax.random.PRNGKey(k)
        want = ch.element_keep_jnp(key, 1024, 25, shuffle=True)
        got = reference.ge_keep(key, 1024, 25, True, ge)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
