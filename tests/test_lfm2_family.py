"""The LFM2-MoE family's benchmark files against the program: the
checkpoint `families/lfm2_moe.py` describes is the one the loader reads,
the configuration is the catalog's with nothing cut, and the byte counts at
the published widths are the ones the configuration file's memory
arithmetic and ISSUE 48 state. And the loader's refusal of a stack of mixed
layers under a family that does not serve it."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import lfm2_moe as lf
from dynamo_tpu.models.loader import (config_from_hf,
                                      load_llama_params_device)
from tests import lfm2_toy as toy_
from tests.sdar_toy import CHIP

CONFIG_FILE = os.path.join(CHIP, "configs", "lfm2-8b-a1b-int8.json")
BENCH_KEYS = ("deployment", "assumed", "source", "family", "weights")


@pytest.fixture(scope="module")
def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    from lib import family as fam

    return fam.load("families", {"family": "lfm2_moe"})


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("lfm2-toy"))
    toy_.write_checkpoint(path)
    return path


def test_the_loader_reads_the_checkpoint_the_benchmark_writes(toy, family):
    cfg = config_from_hf(toy, dtype=jnp.float32)
    assert isinstance(cfg, lf.Lfm2MoeConfig)
    assert cfg.operators == ("conv", "conv", "attn", "conv", "attn", "conv")
    assert (cfg.num_experts, cfg.experts_per_token, cfg.routed_scaling,
            cfg.router_norm_eps, cfg.router_scoring, cfg.expert_act) \
        == (8, 2, 1.0, 1e-6, "sigmoid", "swiglu")
    # every tensor the family writes is read, and no other: no lm_head
    specs = family.tensor_specs(toy_.CONFIG)
    assert sorted(lf.checkpoint_names(cfg)) == sorted(n for n, _, _ in specs)
    assert not any("lm_head" in n for n, _, _ in specs)
    params = load_llama_params_device(toy, cfg)
    layers = params["layers"]
    assert layers["conv"]["in_proj"].shape == (4, 256, 768)
    assert layers["conv"]["conv_w"].shape == (4, 3, 256)
    assert layers["conv"]["conv_w"].dtype == jnp.float32
    assert layers["attn"]["wk"].shape == (2, 256, 128)
    assert layers["attn"]["q_norm"].shape == (2, 64)
    assert layers["moe"]["router"].shape == (4, 256, 8)
    assert layers["moe"]["router_bias"].shape == (4, 8)
    assert params["lm_head"].shape == (256, 300)


def test_the_configuration_is_the_catalogs_with_nothing_cut(published):
    cfg = lf.config_from_hf({k: v for k, v in published.items()
                             if k not in BENCH_KEYS})
    assert cfg.num_layers == 24 and cfg.count("conv") == 18
    assert [l for l, op in enumerate(cfg.operators) if op == "attn"] \
        == [2, 6, 10, 14, 18, 21]
    assert (cfg.hidden_size, cfg.vocab_size, cfg.rms_eps, cfg.rope_theta) \
        == (2048, 65536, 1e-5, 1e6)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.kv_fold) \
        == (32, 8, 64, 2)
    assert (cfg.num_dense_layers, cfg.dense_size, cfg.num_experts,
            cfg.experts_per_token, cfg.intermediate_size, cfg.conv_kernel) \
        == (2, 7168, 32, 4, 1792, 3)
    assert cfg.num_moe_layers == 22 and cfg.qk_norm
    with open(os.path.join(os.path.dirname(CHIP), os.pardir,
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"]
             if c["file"].endswith("lfm2-8b-a1b-int8.json")][0]
    assert entry["reduced"] == [] and published["deployment"]["reduced"] == {}
    # every per-layer metric this family brought names its own cell only
    new = [m for m in bench["per_layer"]
           if m["name"] in ("moe_gmm_swiglu_hbm_roofline",
                            "attn_d64_hbm_roofline")]
    assert [m["workloads"] for m in new] == [["lfm2-8b-a1b.rag-decode"]] * 2


def test_byte_counts_at_the_published_widths(published, family):
    """ISSUE 48's arithmetic, recounted."""
    per = family.layer_params(published)
    assert per["expert"] == 3 * 2048 * 1792 == 11_010_048
    assert per["dense"] - 2048 == 3 * 2048 * 7168 == 44_040_192
    assert per["conv"] - 2048 == 2048 * 6144 + 2048 * 2048 + 2048 * 3
    assert per["full_attention"] - 2048 - 128 \
        == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert round(per["moe"] / 1e6, 1) == 352.4
    assert per["embed"] == 134_217_728
    assert round(family.parameters(published) / 1e9, 2) == 8.34
    assert family.kv_token_bytes(published) == 12_288
    assert family.state_bytes(published) == 18 * 2 * 2048 * 2 == 147_456
    assert round(family.experts_hit(published, 64), 2) == 31.99
    step = family.decode_step_bytes(published, 64 * 2400, 64)
    assert round(step / 1e9, 2) == 10.25
    assert round(family.moe_forward_bytes(published, 64) / 1e9, 2) == 7.86
    assert round(family.attn_forward_bytes(published, 64 * 2400, 64) / 1e9,
                 2) == 1.89
    assert family.block_steps(published) == (1, 0)
    # what the program reserves is what the family counts
    from dynamo_tpu.engine.pages import kv_layer_shape, state_slot_bytes

    cfg = lf.config_from_hf({k: v for k, v in published.items()
                             if k not in BENCH_KEYS})
    assert state_slot_bytes(cfg) == family.state_bytes(published)
    # a page of K and of V, two kv heads a row, six attention layers
    assert kv_layer_shape(cfg, 1) == (4, 1, 16, 128)
    assert 6 * 2 * int(np.prod(kv_layer_shape(cfg, 1))) * 2 \
        == 16 * family.kv_token_bytes(published)
    flags = published["deployment"]["worker_flags"]
    assert flags["num-pages"] * 16 == 64 * flags["context-length"]
    assert round(flags["num-pages"] * 16 * 12_288 / 1e9, 2) == 2.82
    # the checkpoint the writer plans is the bf16 size of 8.34 B
    specs = family.tensor_specs(published)
    assert round(2 * sum(int(np.prod(s)) for _, s, _ in specs) / 1e9, 1) \
        == 16.7


def test_the_fills_can_fail_the_program(family):
    fills = family.fills(toy_.CONFIG)
    assert fills["router"]["gain"] < 16     # sigmoid scores not saturated
    assert fills["taps"] == {"fill": "noise", "gain": 4.0, "fan_in": 4}
    assert fills["expert_out"]["fan_in"] == 96
    assert set(fills) >= {"router_bias", "wide", "attn_out", "dense_out"}


@pytest.mark.parametrize("model_type,types,message", [
    ("llama", ["full_attention", "conv"], r"layer_types names \['conv'\]"),
    ("qwen3", ["sliding_attention", "full_attention"],
     r"layer_types names \['sliding_attention'\]"),
    ("lfm2_moe", ["conv", "linear_attention"],
     r"lfm2_moe: layer_types names \['linear_attention'\]"),
])
def test_a_kind_of_layer_the_family_does_not_serve_is_refused_by_name(
        tmp_path, model_type, types, message):
    """A stack of mixed layers used to be loaded "with the llama-family
    loader" after a warning and failed later on a missing tensor, or not at
    all; now `config_from_hf` refuses it at once, naming the kind."""
    hf = {k: v for k, v in toy_.CONFIG.items()
          if k not in ("deployment", "weights", "family", "rehearsal")}
    hf.update(model_type=model_type, layer_types=types,
              num_hidden_layers=len(types),
              architectures=["SomeNewForCausalLM"])
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with pytest.raises(ValueError, match=message):
        config_from_hf(str(tmp_path))


def test_layer_types_of_attention_alone_keep_loading(tmp_path):
    hf = {"architectures": ["Qwen3ForCausalLM"], "model_type": "qwen3",
          "hidden_size": 64, "intermediate_size": 128,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "vocab_size": 300,
          "layer_types": ["full_attention", "full_attention"]}
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    assert config_from_hf(str(tmp_path)).num_layers == 2


def test_a_disaggregated_worker_does_not_start(toy):
    from dynamo_tpu.llm.entrypoint import build_tpu_engine

    engine, card = build_tpu_engine(toy, num_pages=64, max_pages_per_seq=8)
    assert engine.recurrent and card.kv_block_size == 16
    with pytest.raises(ValueError, match="a KVBM tier"):
        build_tpu_engine(toy, num_pages=64, max_pages_per_seq=8,
                         kvbm_host_blocks=8)
