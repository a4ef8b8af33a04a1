"""`benchmarks/chip/families/sdar_moe.py`: its tensor names are the ones the
program's loaders read (a round trip through the checkpoint the benchmark
writes, host loader and device loader alike), and its byte counts at the
published widths are the configuration file's arithmetic.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.loader import (config_from_hf, load_llama_params,
                                      load_llama_params_device)
from tests import sdar_toy
from tests.sdar_toy import BLOCK, CHIP


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(CHIP, "configs",
                           "sdar-30b-a3b-chat-int8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family(published):
    from lib import family as fam

    return fam.load("families", published)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sdar-toy"))
    sdar_toy.write_checkpoint(path)
    return path


def test_every_tensor_the_family_names_is_read(ckpt, family):
    from lib import ckpt as writer

    cfg = config_from_hf(ckpt, dtype=jnp.float32, attn_block=BLOCK)
    params = load_llama_params(ckpt, cfg)
    named = {n: shape for n, shape, _ in
             family.tensor_specs(writer.hf_config(sdar_toy.CONFIG))}
    assert len(named) == 3 + 2 * (9 + 8 * 3)
    read = sdar_toy.reader(ckpt)
    layers = params["layers"]
    for i in range(2):
        p = f"model.layers.{i}."
        pairs = [("self_attn.q_proj", layers["wq"][i]),
                 ("self_attn.k_proj", layers["wk"][i]),
                 ("self_attn.v_proj", layers["wv"][i]),
                 ("self_attn.o_proj", layers["wo"][i]),
                 ("mlp.gate", layers["router"][i])]
        for e in range(8):
            pairs += [(f"mlp.experts.{e}.gate_proj", layers["w_gate"][i, e]),
                      (f"mlp.experts.{e}.up_proj", layers["w_up"][i, e]),
                      (f"mlp.experts.{e}.down_proj", layers["w_down"][i, e])]
        for name, ours in pairs:
            assert p + name + ".weight" in named
            np.testing.assert_array_equal(
                np.asarray(ours, np.float32),
                np.asarray(read.numpy(p + name + ".weight"), np.float32).T)
        for name, key in (("self_attn.q_norm", "q_norm"),
                          ("self_attn.k_norm", "k_norm"),
                          ("input_layernorm", "attn_norm"),
                          ("post_attention_layernorm", "mlp_norm")):
            assert named[p + name + ".weight"] == layers[key][i].shape


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_device_loader_equals_host_loader(ckpt, quantize):
    cfg = config_from_hf(ckpt, attn_block=BLOCK)
    host = load_llama_params(ckpt, cfg)
    dev = load_llama_params_device(ckpt, cfg, quantize=quantize)
    assert set(dev["layers"]) == set(host["layers"])
    for key in ("q_norm", "k_norm", "router"):
        np.testing.assert_array_equal(
            np.asarray(dev["layers"][key], np.float32),
            np.asarray(host["layers"][key], np.float32))
    gate = dev["layers"]["w_gate"]
    if quantize:
        assert gate.q.dtype == jnp.int8 and gate.q.shape == (2, 8, 64, 96)
        assert gate.s.shape == (2, 8, 1, 96)
        gate = gate.q.astype(jnp.float32) * gate.s
    np.testing.assert_allclose(
        np.asarray(gate, np.float32),
        np.asarray(host["layers"]["w_gate"], np.float32),
        atol=0.01 if quantize else 0)


def test_published_widths_are_the_catalogs(published):
    want = dict(hidden_size=2048, num_attention_heads=32,
                num_key_value_heads=4, head_dim=128, num_experts=128,
                num_experts_per_tok=8, moe_intermediate_size=768,
                vocab_size=151936, rope_theta=1000000, rms_norm_eps=1e-06,
                intermediate_size=6144, max_position_embeddings=32768,
                tie_word_embeddings=False, norm_topk_prob=True,
                decoder_sparse_step=1, mlp_only_layers=[],
                model_type="sdar_moe", num_hidden_layers=10)
    assert {k: published[k] for k in want} == want


def test_byte_counts_at_the_published_widths(published, family):
    from lib import ckpt as writer

    specs = family.tensor_specs(writer.hf_config(published))
    count = {}
    for name, shape, _ in specs:
        kind = ("expert" if ".experts." in name else
                "embed" if "embed_tokens" in name else
                "head" if name == "lm_head.weight" else
                "attention" if "_proj" in name else "other")
        count[kind] = count.get(kind, 0) + int(np.prod(shape))
    assert count["expert"] == 10 * 603_979_776           # 603.98 M a layer
    assert count["attention"] == 10 * 18_874_368         # 18.87 M a layer
    assert count["embed"] == count["head"] == 311_164_928
    served = count["expert"] + count["attention"] + 2 * 2 * count["embed"]
    assert round(served / 1e9, 2) == 7.47                # + router: 7.48 GB
    on_disk = 2 * sum(count.values())
    assert round(on_disk / 1e9, 1) == 13.7
    kv_token = 10 * 2 * 4 * 128 * 2
    assert kv_token == 20480 and 12288 * 16 * kv_token == 4_026_531_840


def test_decode_step_bytes_is_five_forwards_a_block(published, family):
    lanes, kv_tokens = 64, 64 * 766
    step = family.decode_step_bytes(published, kv_tokens, lanes)
    experts = family.moe_forward_bytes(published, lanes * 4)
    # all 128 experts are reached at 256 rows x 8 draws
    assert family.experts_hit(published, 256) > 127.99
    attention = 10 * (18_874_368 + 128 * 2048 * 2)
    kv = (kv_tokens + 256) * 20480
    head = 2048 * 151936 * 2
    weights_only = 10 * 603_979_776
    want = (5 * (attention + weights_only + kv) + 4 * head) / 4
    assert abs(step - want) / want < 1e-6
    # a forward: 6.23 GB of weights + ~1 GB of KV, 9 ms at 819 GB/s
    forward = attention + weights_only + kv + head
    assert 8.5e-3 < forward / 819e9 < 10e-3
    moved = experts - weights_only
    assert 0 < moved < 0.05 * weights_only               # rows in and out
    assert family.block_steps(published) == (4, 4)


def test_few_rows_reach_few_experts(published, family):
    one = family.moe_forward_bytes(published, 1)
    assert 7.9 < family.experts_hit(published, 1) <= 8.0
    assert one < 0.07 * family.moe_forward_bytes(published, 256)
