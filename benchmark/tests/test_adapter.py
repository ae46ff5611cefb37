"""The dense adapter: every key of a configuration file is mapped, checked
or refused by name; other widths map through unchanged."""

import pytest

from benchmark.adapters import llama_dense
from benchmark.harness import common, flops


def _cfg(name="mistral7b-l2"):
    return common.load_json("configs", f"{name}.json")


def test_maps_every_width_of_the_file():
    mc = llama_dense.model_config(_cfg(), remat_block=True, seq_len=8192)
    assert (mc.d_model, mc.d_ff, mc.n_head, mc.n_kv_head, mc.head_dim,
            mc.vocab_size, mc.n_layer, mc.sliding_window, mc.max_seq_len,
            mc.remat_block, mc.num_experts) == (
        4096, 14336, 32, 8, 128, 32000, 2, 4096, 8192, True, 0)


def test_other_widths_map_through_and_head_dim_may_be_left_out():
    # internlm2-1_8b's sizes: d 2048, 16 heads over 8 KV heads, vocab 92,544
    cfg = {"hidden_size": 2048, "intermediate_size": 8192,
           "num_attention_heads": 16, "num_key_value_heads": 8,
           "num_hidden_layers": 4, "vocab_size": 92544,
           "rope_theta": 1000000, "rms_norm_eps": 1e-5,
           "adapter": "llama_dense"}
    mc = llama_dense.model_config(cfg, remat_block=False, seq_len=4096)
    assert (mc.d_model, mc.d_ff, mc.n_head, mc.n_kv_head, mc.head_dim,
            mc.vocab_size, mc.sliding_window, mc.rope_theta) == (
        2048, 8192, 16, 8, 128, 92544, 0, 1e6)
    assert flops.heads(cfg) == (16, 8, 128)
    assert flops.matmul_params(cfg)["layer"] == (
        2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192)


@pytest.mark.parametrize("extra,word", [
    ({"num_experts": 64, "num_experts_per_tok": 8}, "num_experts"),
    ({"kv_lora_rank": 512}, "kv_lora_rank"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"head_dim": 64}, "head_dim"),
])
def test_what_the_adapter_cannot_compute_is_refused_by_name(extra, word):
    with pytest.raises(ValueError, match=word):
        llama_dense.model_config(dict(_cfg(), **extra), remat_block=False,
                                 seq_len=64)


def test_grad_leaves_round_trip():
    params = {"embed": 1, "ln_f": 2, "lm_head": 3, "layers": [
        {"wq": 10, "wk": 11, "wv": 12, "wo": 13},
        {"wq": 20, "wk": 21, "wv": 22, "wo": 23}]}
    leaves = llama_dense.grad_leaves(params)
    assert sorted(leaves) == ["embed", "layers.0.wk", "layers.0.wq",
                              "layers.0.wv", "layers.1.wk", "layers.1.wq",
                              "layers.1.wv"]
    assert llama_dense.with_leaves(params, leaves) == params
    swapped = llama_dense.with_leaves(params, dict(leaves, embed=99))
    assert swapped["embed"] == 99 and swapped["layers"][1]["wo"] == 23
