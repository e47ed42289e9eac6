"""The hybrid cell's pieces: its FLOP counts at the published widths, its
per-layer readers on hand-made readings, and its configuration against
the published one."""

import json
import os

import pytest

from bench_testing import ROOT, run

from bench import common, counts_hybrid

CFG = common.load_json(os.path.join(ROOT, "bench/configs/myia-granite4h-micro.json"))


def test_flop_counts_at_the_published_widths():
    """Per token, forward: each Mamba layer 156,565,504 (the SSD
    4,259,840 of it), the attention layer at 2,048 tokens causally
    130,023,424, the head 411,041,792; nine, one and one of them."""
    assert counts_hybrid.ssd_flops_per_token(CFG) == 4_259_840
    assert counts_hybrid.mamba_layer_flops_per_token(CFG) == 156_565_504
    assert counts_hybrid.attention_layer_flops_per_token(CFG, 2048) == 130_023_424
    assert counts_hybrid.head_flops_per_token(CFG) == 411_041_792
    fwd = 9 * 156_565_504 + 130_023_424 + 411_041_792
    assert counts_hybrid.granite4h_forward_flops_per_token(CFG, 2048) == fwd == 1_950_154_752
    assert counts_hybrid.granite4h_train_flops_per_token(CFG, 2048) == 3 * fwd


def test_flop_counts_by_hand():
    # D 4, F 8, H 2 heads of P 2, N 3, Q 4, V 10; KV 1 head of 2 (2 heads of 2)
    cfg = {"hidden_size": 4, "shared_intermediate_size": 8, "mamba_n_heads": 2,
           "mamba_d_head": 2, "mamba_d_state": 3, "mamba_chunk_size": 4, "vocab_size": 10,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "layer_types": ["mamba", "attention", "mamba"], "num_hidden_layers": 3}
    ssd = 2 * 4 * 3 + 2 * 2 * 4 * 2 + 4 * 2 * 2 * 3  # CB^T, (L o CB^T) x, states, output
    assert counts_hybrid.ssd_flops_per_token(cfg) == ssd == 104
    mamba = 2 * 4 * (8 + 6 + 2) + 2 * 4 * 4 + 6 * 4 * 8 + ssd
    assert counts_hybrid.mamba_layer_flops_per_token(cfg) == mamba
    att = 2 * 4 * (8 + 4) + 6 * 4 * 8 + 2 * 4 * 16
    assert counts_hybrid.attention_layer_flops_per_token(cfg, 16) == att
    assert counts_hybrid.granite4h_train_flops_per_token(cfg, 16) == 3 * (
        2 * mamba + att + 2 * 4 * 10
    )


def _reader(name):
    return run.load_reader(name)


def test_step_mfu_reader():
    ctx = {"steps": 10, "tokens_per_step": 4096, "window_s": 4.0, "chips": 1,
           "config": CFG, "traffic": {"seq": 2048}, "peaks": {"bf16_flops_per_s": 197e12}}
    flops = 3 * 1_950_154_752 * 4096 * 10
    assert _reader("step_mfu.train_hybrid")(ctx) == pytest.approx(100 * flops / 4.0 / 197e12)
    assert _reader("step_mfu.train_hybrid")({}) is None


def test_timing_and_idle_readers():
    assert _reader("myia_over_jax.train_hybrid")({"myia_vag_s": 3.0, "jax_vag_s": 2.0}) == 1.5
    assert _reader("myia_over_jax.train_hybrid")({"myia_vag_s": 3.0}) is None
    ctx = {"myia_layer_vag_s": 1.0, "jax_layer_vag_s": 4.0}
    assert _reader("mamba_over_jax.train_hybrid")(ctx) == 0.25
    assert _reader("mamba_over_jax.train_hybrid")({}) is None
    assert _reader("device_idle.train_hybrid")({"trace": {"idle_share": 0.03}}) == 3.0
    assert _reader("device_idle.train_hybrid")({}) is None


def test_saved_carry_reader_sums_the_ad_spans_and_needs_the_counter():
    spans = [("parse", {"fn": "f"}), ("ad.grad", {"graph": "f", "loops": 4,
                                                  "saved_carry_bytes": 1_500_000_000}),
             ("ad.grad", {"graph": "g", "loops": 0, "saved_carry_bytes": 0})]
    assert _reader("saved_carry_gb.train_hybrid")({"setup_span_attrs": spans}) == 1.5
    # a program whose ad.grad span has no counter (or no span) reads nothing
    old = [("ad.grad", {"graph": "f"})]
    assert _reader("saved_carry_gb.train_hybrid")({"setup_span_attrs": old}) is None
    assert _reader("saved_carry_gb.train_hybrid")({}) is None


def test_pipeline_reader_is_pipeline_s():
    spans = [("parse", 0.0, 1.0), ("ad.grad", 0.5, 3.0), ("xla.compile", 2.0, 2.5)]
    assert _reader("pipeline_s.train_hybrid")({"setup_spans": spans}) == 2.5


def test_configuration_is_the_published_one_cut_in_depth():
    """Every key of the published config.json is in the file; only
    ``num_hidden_layers`` differs, and ``layer_types`` is kept whole."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352,
    }
    differs = sorted(k for k, v in published.items() if CFG[k] != v)
    assert differs == CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 10
    types = CFG["layer_types"]
    assert len(types) == 40 and types[5::10] == ["attention"] * 4
    assert types[:10].count("mamba") == 9
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in bench["configs"] if c["name"] == CFG["name"]]
    assert entry["reduced"] == CFG["reduced"] and entry["source"] == CFG["source"]
