"""Operations and bytes against the program's own counts, and the seeded
weights against the program's parameter layout and the reference's."""

import json

import jax
import numpy as np
import pytest

from bench import flops, model, reference
from tinycells import ROOT, TINY

CONFIGS = ["qwen1.5-0.5b", "codeqwen1.5-7b"]


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


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

    conf = dict(TINY, name="tiny", source="test")
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
    conf = dict(TINY, name="tiny", source="test", tie_word_embeddings=False)
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
