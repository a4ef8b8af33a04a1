"""The Nemotron-H family's benchmark files against the program: the
checkpoint `families/nemotron_h.py` describes is the one the loader reads,
the pattern's table, and the byte counts at the published widths, which the
configuration file's memory arithmetic and ISSUE 41 state."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.quant import QTensor
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.loader import (config_from_hf,
                                      load_llama_params_device)
from tests import nemotron_toy as toy_
from tests.sdar_toy import CHIP

CONFIG_FILE = os.path.join(CHIP, "configs",
                           "nemotron-3-nano-30b-a3b-int8.json")


@pytest.fixture(scope="module")
def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    from lib import family as fam

    return fam.load("families", {"family": "nemotron_h"})


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("nemotron-toy"))
    toy_.write_checkpoint(path)
    return path


def test_the_loader_reads_the_checkpoint_the_benchmark_writes(toy, family):
    cfg = config_from_hf(toy, dtype=jnp.float32)
    assert isinstance(cfg, nh.NemotronHConfig)
    assert (cfg.pattern, cfg.num_layers) == (toy_.PATTERN, 6)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.routed_scaling,
            cfg.shared_expert_size) == (8, 2, 2.5, 160)
    # every tensor the family writes is read, and no other
    specs = family.tensor_specs(toy_.CONFIG)
    assert sorted(nh.checkpoint_names(cfg)) == sorted(n for n, _, _ in specs)
    params = load_llama_params_device(toy, cfg)
    layers = params["layers"]
    assert layers["mamba"]["in_proj"].shape == (3, 64, 64 + 128 + 4)
    assert layers["mamba"]["conv_w"].shape == (3, 4, 128)
    assert layers["attn"]["wq"].shape == (1, 64, 64)
    # the expert width 96 is padded to the kernel's tile, with zeros
    assert layers["moe"]["w_up"].shape == (2, 8, 64, 128)
    assert layers["moe"]["w_down"].shape == (2, 8, 128, 64)
    read = toy_.reader(toy)
    want = np.asarray(read.numpy(
        "backbone.layers.4.mixer.experts.5.down_proj.weight"), np.float32)
    np.testing.assert_array_equal(layers["moe"]["w_down"][1, 5, :96], want.T)
    np.testing.assert_array_equal(layers["moe"]["w_down"][1, 5, 96:], 0.0)
    np.testing.assert_array_equal(
        layers["mamba"]["A_log"][2],
        np.asarray(read.numpy("backbone.layers.5.mixer.A_log"), np.float32))
    np.testing.assert_array_equal(
        layers["mamba"]["conv_w"][0].T,
        np.asarray(read.numpy("backbone.layers.0.mixer.conv1d.weight"),
                   np.float32)[:, 0])


def test_int8_is_every_projection_and_nothing_else(toy):
    cfg = config_from_hf(toy)
    layers = load_llama_params_device(toy, cfg, quantize="int8")["layers"]
    quantized = {f"{kind}.{k}" for kind, d in layers.items()
                 for k, v in d.items() if isinstance(v, QTensor)}
    assert quantized == {
        "mamba.in_proj", "mamba.out_proj", "attn.wq", "attn.wk", "attn.wv",
        "attn.wo", "moe.w_up", "moe.w_down", "moe.w_shared_up",
        "moe.w_shared_down"}
    assert layers["moe"]["w_up"].q.dtype == jnp.int8
    assert layers["moe"]["router"].dtype == jnp.float32
    with pytest.raises(ValueError, match="weight-only int8"):
        load_llama_params_device(toy, cfg, quantize="int4")


def test_the_patterns_table():
    cfg = nh.NemotronHConfig.tiny(pattern="MEMEM*EME")
    assert cfg.table == (
        ("mamba", 0, 0), ("moe", 0, -1), ("mamba", 1, 1), ("moe", 1, -1),
        ("mamba", 2, 2), ("attn", 0, 3), ("moe", 2, -1), ("mamba", 3, 4),
        ("moe", 3, -1))
    assert (cfg.count("mamba"), cfg.num_moe_layers, cfg.count("attn")) \
        == (4, 4, 1)
    kc, vc = nh.init_cache(cfg, 6, 3)
    assert len(kc) == len(vc) == 5          # expert layers keep nothing
    assert vc[0].dtype == jnp.float32 and vc[0].shape == (3, 4, 16, 16)
    assert kc[3].shape == vc[3].shape == (2, 6, 4, 16)
    with pytest.raises(ValueError, match="pattern"):
        nh.NemotronHConfig.tiny(pattern="MEX")
    with pytest.raises(ValueError, match="pattern"):
        nh.NemotronHConfig.tiny(pattern="ME", num_layers=3)


def test_the_configuration_is_the_published_one_cut_by_depth(published):
    cfg = nh.config_from_hf({k: v for k, v in published.items()
                             if k not in ("deployment", "assumed")})
    assert cfg.pattern == "MEMEM*EME"
    assert (cfg.hidden_size, cfg.vocab_size, cfg.rms_eps) \
        == (2688, 131072, 1e-5)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel, cfg.chunk_size) \
        == (64, 64, 8, 128, 4, 128)
    assert (cfg.d_inner, cfg.conv_dim) == (4096, 6144)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.intermediate_size,
            cfg.shared_expert_size, cfg.routed_scaling) \
        == (128, 6, 1856, 3712, 2.5)
    with open(os.path.join(os.path.dirname(CHIP), os.pardir,
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"]
             if c["file"].endswith("nemotron-3-nano-30b-a3b-int8.json")][0]
    assert entry["reduced"] == ["num_hidden_layers",
                                "hybrid_override_pattern"]
    assert set(published["deployment"]["reduced"]) == set(entry["reduced"])


def test_byte_counts_at_the_published_widths(published, family):
    """ISSUE 41's arithmetic, recounted."""
    per = family.layer_params(published)
    assert per["mamba"] == 2688 * 10304 + 4096 * 2688 + 2688 + 4096 \
        + 6144 * 5 + 3 * 64 == 38_744_896
    assert per["attn"] == 23_399_040 and per["expert"] == 9_977_856
    assert per["shared"] == 19_955_712 and per["embed"] == 352_321_536
    assert round(per["moe"] / 1e6, 1) == 1297.5
    layers = 4 * per["mamba"] + 4 * per["moe"] + per["attn"]
    assert round(layers / 1e9, 2) == 5.37
    assert round((layers + 4 * per["embed"]) / 1e9, 2) == 6.78   # as served
    assert round(2 * (layers + 2 * per["embed"]) / 1e9, 1) == 12.1  # bf16
    assert family.state_bytes(published) == (2_097_152, 36_864)
    assert round(129 * 4 * sum(family.state_bytes(published)) / 1e9, 2) \
        == 1.10
    assert round(family.experts_hit(published, 128), 1) == 127.7
    step = family.decode_step_bytes(published, 128 * 2000, 128)
    assert round(step / 1e9, 1) == 8.5
    assert round(family.ssm_forward_bytes(published, 128) / 1e9, 2) == 2.17
    assert round(family.moe_forward_bytes(published, 128) / 1e9, 2) == 5.15
    assert family.block_steps(published) == (1, 0)
    # what the program reserves a slot is what the family counts a sequence
    from dynamo_tpu.engine.pages import state_slot_bytes

    cfg = nh.config_from_hf({k: v for k, v in published.items()
                             if k not in ("deployment", "assumed")})
    assert state_slot_bytes(cfg) == 4 * sum(family.state_bytes(published))
    # and the checkpoint the writer plans is the bf16 size stated
    specs = family.tensor_specs(published)
    assert round(2 * sum(int(np.prod(s)) for _, s, _ in specs) / 1e9, 1) \
        == 12.1


def test_the_fills_can_fail_the_program(family):
    fills = family.fills(toy_.CONFIG)
    assert fills["a_log"] == {"fill": "noise", "gain": 8.0, "fan_in": 1}
    assert fills["router"]["gain"] < 16     # sigmoid scores not saturated
    assert set(fills) >= {"router_bias", "dt_bias", "conv", "skip"}


def test_a_disaggregated_worker_does_not_start(toy):
    from dynamo_tpu.llm.entrypoint import build_tpu_engine

    engine, card = build_tpu_engine(toy, num_pages=64, max_pages_per_seq=8)
    assert engine.recurrent and card.kv_block_size == 16
    with pytest.raises(ValueError, match="disaggregated role"):
        engine.refuse_if_recurrent(
            "a disaggregated role (a KV export or import)")
    with pytest.raises(ValueError, match="a KVBM tier"):
        build_tpu_engine(toy, num_pages=64, max_pages_per_seq=8,
                         kvbm_host_blocks=8)
