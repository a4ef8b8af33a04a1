"""Main-path kernels and step programs compiled for the chip, without it.

The TPU compiler is installed here and compiles for a *described*
v5e:2x2: what Mosaic refuses (tiling, VMEM) or what does not fit shows
up in this file instead of costing chip time. Shapes are Llama-3-8B
widths (32 q heads, 8 kv heads, head_dim 128, page 16, bf16 KV). A
compile that passes is not a chip run — `chip_smoke.py` is.

The topology is described inside the `topo` fixture only: libtpu loads
once per process, so nothing here may touch it at import/collection
time (every xdist worker imports this file; one runs it).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, KVH, D, PAGE, PAGES, MAX_PAGES = 32, 8, 128, 16, 2048, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip (the next one warns
    and recompiles): keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def sds(one_chip, no_persistent_cache):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kv(sds):
    return sds((KVH, PAGES, PAGE, D)), sds((KVH, PAGES, PAGE, D))


@pytest.mark.parametrize("batch", [4, 32])
def test_paged_attention_decode_kernel(sds, batch):
    from dynamo_tpu.engine.attention import _pallas_decode

    kc, vc = _kv(sds)
    text = _compiled_text(
        _pallas_decode, sds((batch, H, D)), kc, vc,
        sds((batch,), jnp.int32), sds((batch, MAX_PAGES), jnp.int32))
    assert "tpu_custom_call" in text
    assert "paged_decode_attention" in text   # the name a device trace shows


@pytest.mark.parametrize("heads,kv_heads,max_pages,batch", [
    (28, 4, 288, 8),      # Qwen2.5-7B: 7 q heads to a kv head, 4608 tokens
    (8, 2, 128, 32),      # a tp=4 shard of Mistral-7B / Llama-3-8B
    (7, 1, 288, 8),       # a tp=4 shard of Qwen2.5-7B
    (10, 2, 128, 8),      # a tp=4 shard of Qwen2.5-14B: 5 to a kv head
    (32, 8, 128, 64),     # batch 64: two grid steps of 32 lanes
], ids=["qwen2.5-7b", "tp4-8kv", "tp4-qwen7b", "tp4-qwen14b", "two-steps"])
def test_paged_decode_attention_geometries(sds, heads, kv_heads, max_pages,
                                           batch):
    from dynamo_tpu.engine.attention import paged_decode_attention

    cache = sds((kv_heads, PAGES, PAGE, D))
    text = _compiled_text(
        paged_decode_attention, sds((batch, heads, D)), cache, cache,
        sds((batch,), jnp.int32), sds((batch, max_pages), jnp.int32))
    assert "paged_decode_attention" in text


@pytest.mark.parametrize("batch", [4, 32])
def test_paged_kv_write_kernel(sds, batch):
    from dynamo_tpu.engine.kernels import paged_kv_write

    kc, vc = _kv(sds)
    text = _compiled_text(
        paged_kv_write, kc, vc, sds((batch, KVH, D)), sds((batch, KVH, D)),
        sds((batch,), jnp.int32), sds((batch,), jnp.int32))
    assert "tpu_custom_call" in text
    assert "kv_write_rows" in text      # the name a device trace shows


@pytest.mark.parametrize("n_pages", [8, 256])   # 1 x 128 and 32 x 128 tokens
def test_paged_kv_write_pages_kernel(sds, n_pages):
    from dynamo_tpu.engine.kernels import paged_kv_write_pages

    kc, vc = _kv(sds)
    text = _compiled_text(
        paged_kv_write_pages, kc, vc, sds((n_pages, KVH, PAGE, D)),
        sds((n_pages, KVH, PAGE, D)), sds((n_pages,), jnp.int32))
    assert "tpu_custom_call" in text
    assert "kv_write_pages" in text


@pytest.mark.parametrize("rows,lanes", [(8, 4), (64, 32)])
def test_ragged_paged_attention_kernel(sds, rows, lanes):
    from dynamo_tpu.engine.ragged import ragged_paged_attention

    kc, vc = _kv(sds)
    text = _compiled_text(
        ragged_paged_attention, sds((rows, H, D)), kc, vc,
        sds((rows,), jnp.int32), sds((rows,), jnp.int32),
        sds((lanes, MAX_PAGES), jnp.int32))
    assert "tpu_custom_call" in text
    assert "ragged_paged_attention" in text


# -- the engine's own jitted steps, 8B widths, depth cut to 2 layers --------

# jax.named_scope blocks of models/llama.py: they reach the compiled
# program as op_name metadata, which a trace with the HLO proto shows
LAYER_SCOPES = ("attn_qkv", "kv_write", "attn_core", "attn_out", "mlp",
                "lm_head")


def _missing(text: str, names) -> list[str]:
    return [n for n in names if f"/{n}/" not in text]


def _greedy_tail_reads(text: str, operand: str, absent=()) -> None:
    """The sampler's greedy branch is the kernel `greedy_tail` over
    `operand`, the logits as they were written (a head's product is bf16):
    the computation that holds the kernel holds none of the `absent`
    arrays (no widening ahead of it, no log-softmax beside it, no gather
    of the lanes' rows)."""
    held = [c for c in text.split("\n}\n")
            if 'custom_call_target="tpu_custom_call"' in c
            and "/greedy_tail/" in c]
    assert held, "no greedy_tail kernel in the compiled program"
    for computation in held:
        assert operand in computation
        for shape in absent:
            assert shape not in computation, shape


def _reads_the_heads_product(text: str, rows: int, vocab: int) -> None:
    _greedy_tail_reads(text, f"bf16[{rows},{vocab}]",
                       absent=(f"f32[{rows},{vocab}]",))


def test_first_token_sampler_reduces_the_rows_of_the_round(sds, pallas_impl):
    """A prefill round of 8 sequences hands its (8, V) float32 logits to
    the first-token sampler of a 128-lane engine with the rows the lanes
    read: a greedy batch runs the kernel over the 8 rows there are and
    gathers two numbers a lane, never 128 rows of logits."""
    from dynamo_tpu.engine.sampling import sample_tokens_lp

    b, v, i32, u32, f32 = 128, 131072, jnp.int32, jnp.uint32, jnp.float32
    text = sample_tokens_lp.lower(
        sds((8, v), f32), sds((b,), u32), sds((b,), u32), sds((b,), f32),
        sds((b,), f32), sds((b,), i32), sds((b,), f32),
        rows=sds((b,), i32), topk_lp=0).compile().as_text()
    _greedy_tail_reads(text, f"f32[8,{v}]", absent=(f"[{b},{v}]",))


@pytest.fixture
def pallas_impl(monkeypatch):
    # use_pallas() asks the default backend, which is the CPU here:
    # steer it in the test, as the worker on the chip would decide
    from dynamo_tpu.engine import attention

    monkeypatch.setattr(attention, "_impl", "pallas")


# widths of the benchmark's two configurations (benchmarks/chip/configs/)
WIDTHS = {
    "llama3-8b": dict(),
    "mistral-7b": dict(vocab_size=32768, rope_theta=1e6),
    "qwen2.5-7b": dict(vocab_size=152064, hidden_size=3584,
                       intermediate_size=18944, num_heads=28, num_kv_heads=4,
                       rope_theta=1e6, attention_bias=True),
}


@pytest.fixture
def model_of(sds):
    """(cfg, params, k_cache, v_cache) as shapes on the described chip,
    2 layers: int8 weights exactly as `--quantize int8` serves them."""
    from dynamo_tpu.engine.quant import quantize_params
    from dynamo_tpu.models.llama import (LlamaConfig, init_cache,
                                         init_params)

    def make(widths="llama3-8b", max_pages=MAX_PAGES):
        cfg = LlamaConfig.llama3_8b(num_layers=2, max_pages_per_seq=max_pages,
                                    **WIDTHS[widths])

        def on_chip(tree):
            return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

        params = on_chip(jax.eval_shape(
            lambda k: quantize_params(init_params(k, cfg), mode="int8"),
            jax.random.PRNGKey(0)))
        kc, vc = on_chip(jax.eval_shape(lambda: init_cache(cfg, PAGES)))
        return cfg, params, kc, vc

    return make


@pytest.fixture
def model(model_of):
    return model_of()


def test_engine_decode_burst_holds_the_kernels(sds, model, pallas_impl):
    from dynamo_tpu.models.llama import decode_multi_step

    cfg, params, kc, vc = model
    b, i32, u32, f32 = 8, jnp.int32, jnp.uint32, jnp.float32
    compiled = decode_multi_step.lower(
        params, kc, vc, sds((b,), i32), sds((b,), i32),
        sds((b, MAX_PAGES), i32), sds((b,), jnp.bool_), sds((b,), u32),
        sds((b,), u32), sds((b,), f32), sds((b,), f32), sds((b,), i32),
        cfg, 8, topk_lp=0).compile()
    text = compiled.as_text()
    # per layer: the row KV write and the paged-attention decode
    assert text.count("tpu_custom_call") >= 2 * cfg.num_layers
    assert text.count("kv_write_rows") >= cfg.num_layers
    assert text.count("paged_decode_attention") >= cfg.num_layers
    assert not _missing(text, LAYER_SCOPES + ("sample",))
    _reads_the_heads_product(text, b, cfg.vocab_size)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("widths,bp,t,max_pages,temp_limit", [
    ("llama3-8b", 1, 128, MAX_PAGES, 32 << 20),
    # the rounds of the benchmark's cells; temporaries measured in PR 32
    # (10 / 193 / 63 MB; the einsum's f32 scores took 299 / 2492 / 697)
    ("qwen2.5-7b", 1, 512, 288, 32 << 20),
    ("qwen2.5-7b", 8, 512, 288, 256 << 20),
    ("mistral-7b", 8, 256, 128, 128 << 20),
], ids=["llama3-8b-1x128", "qwen-1x512", "qwen-8x512", "mistral-8x256"])
def test_engine_prefill_chunk_holds_the_page_write(
        sds, model_of, pallas_impl, widths, bp, t, max_pages, temp_limit):
    from dynamo_tpu.models.llama import prefill_batch

    cfg, params, kc, vc = model_of(widths, max_pages)
    i32 = jnp.int32
    compiled = prefill_batch.lower(
        params, kc, vc, sds((bp, t), i32), sds((bp, max_pages), i32),
        sds((bp,), i32), sds((bp,), i32), cfg, aligned=True).compile()
    text = compiled.as_text()
    # per layer: the page KV write and the prefill attention
    assert text.count("tpu_custom_call") >= 2 * cfg.num_layers
    assert text.count("kv_write_pages") >= cfg.num_layers
    assert text.count("paged_prefill_attention") >= cfg.num_layers
    assert not _missing(text, LAYER_SCOPES)
    # no score tensor over the whole context, whatever leads it
    assert not re.search(rf"f32\[[0-9,]*{t},{max_pages * PAGE}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


@pytest.mark.parametrize("step", ["decode_burst", "prefill_chunk"])
def test_tensor_parallel_steps_split_the_kernels(
        topo, no_persistent_cache, pallas_impl, step):
    """tp=4 over the described 2x2: GSPMD cannot partition a Mosaic
    kernel, so the steps must run them per shard (kernels.per_tp_shard)
    and still reduce over tp."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.quant import quantize_params
    from dynamo_tpu.engine.sharding import (cache_sharding, make_mesh,
                                            param_sharding)
    from dynamo_tpu.models.llama import (LlamaConfig, decode_multi_step,
                                         init_cache, init_params,
                                         prefill_batch)

    cfg = LlamaConfig.llama3_8b(num_layers=2, max_pages_per_seq=MAX_PAGES)
    mesh = make_mesh(dp=1, tp=4, devices=list(topo.devices))

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    # as the engine does: bf16 shards placed, then quantised in place
    raw = placed(jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0)),
                 param_sharding(mesh))
    quantise = jax.jit(lambda p: quantize_params(p, mode="int8"))
    params = placed(jax.eval_shape(quantise, raw),
                    quantise.lower(raw).compile().output_shardings)
    caches = jax.eval_shape(lambda: init_cache(cfg, PAGES))
    kc, vc = placed(caches, jax.tree.map(
        lambda _: cache_sharding(mesh), caches))
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    b, i32, u32, f32 = 8, jnp.int32, jnp.uint32, jnp.float32
    with jax.set_mesh(mesh):
        if step == "decode_burst":
            lowered = decode_multi_step.lower(
                params, kc, vc, sds((b,), i32), sds((b,), i32),
                sds((b, MAX_PAGES), i32), sds((b,), jnp.bool_),
                sds((b,), u32), sds((b,), u32), sds((b,), f32),
                sds((b,), f32), sds((b,), i32), cfg, 8, topk_lp=0)
            attention = "paged_decode_attention"
        else:
            lowered = prefill_batch.lower(
                params, kc, vc, sds((b, 256), i32), sds((b, MAX_PAGES), i32),
                sds((b,), i32), sds((b,), i32), cfg, aligned=True)
            attention = "paged_prefill_attention"
        text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2 * cfg.num_layers
    assert text.count(attention) >= cfg.num_layers
    assert "all-reduce" in text


# -- SDAR-30B-A3B widths: the block burst and a prefill round ----------------

SDAR = dict(vocab_size=151936, hidden_size=2048, intermediate_size=768,
            num_heads=32, num_kv_heads=4, head_dim=128, rope_theta=1e6,
            rms_eps=1e-6, num_experts=128, experts_per_token=8,
            qk_norm=True, attn_block=4, mask_token_id=151669,
            max_pages_per_seq=65)


def sdar_model(sds, layers=2, pages=4096):
    """(cfg, params, k_cache, v_cache) as shapes on the described chip at
    the benchmark's SDAR widths: int8 layers and expert stacks, bf16 head
    (vocab above 65536), as `--quantize int8` serves them."""
    from dynamo_tpu.engine.quant import quantize_params
    from dynamo_tpu.models.llama import init_cache, init_params
    from dynamo_tpu.models.mixtral import MoeConfig

    cfg = MoeConfig(num_layers=layers, **SDAR)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda k: quantize_params(init_params(k, cfg), mode="int8"),
        jax.random.PRNGKey(0)))
    kc, vc = on_chip(jax.eval_shape(lambda: init_cache(cfg, pages)))
    return cfg, params, kc, vc


def test_sdar_block_burst_holds_its_kernels(sds, pallas_impl):
    from dynamo_tpu.models.llama import block_decode_multi_step

    cfg, params, kc, vc = sdar_model(sds)
    assert params["layers"]["w_gate"].q.dtype == jnp.int8
    b, i32, f32 = 64, jnp.int32, jnp.float32
    compiled = block_decode_multi_step.lower(
        params, kc, vc, sds((b, 4), i32), sds((b,), i32), sds((b,), i32),
        sds((b, 65), i32), sds((b,), jnp.bool_), sds((b,), jnp.uint32),
        sds((b,), f32), sds((b,), f32), sds((b,), i32), cfg, 2, 4,
        "sequential").compile()
    text = compiled.as_text()
    # a denoising forward and the committing one, each per layer: the
    # block's KV write, decode attention at 32 rows a kv head, and the
    # three grouped products over the int8 expert stacks
    assert text.count("kv_write_block") >= 2 * cfg.num_layers
    assert text.count("paged_decode_attention") >= 2 * cfg.num_layers
    assert text.count("moe_gmm") >= 2 * 3 * cfg.num_layers
    assert not _missing(text, LAYER_SCOPES + (
        "denoise_step", "block_commit", "moe_route", "moe_experts",
        "moe_combine", "sample"))
    # no copy of an expert stack, widened to bf16 or a layer's slice of the
    # int8 one (the kernel indexes the layer itself): (128, 2048, 768) or
    # its transpose
    assert not re.search(r"(bf16|s8)\[128,(2048,768|768,2048)\]", text)
    # 64 lanes x 4 positions of bf16 logits, read once where they stand
    _reads_the_heads_product(text, 4 * b, cfg.vocab_size)
    # 161.6 MB with the head's product handed on as it is; 318.9 MB where
    # the head widened it to float32 for the sampler (the parent of PR 49)
    assert compiled.memory_analysis().temp_size_in_bytes < 240 << 20


@pytest.mark.parametrize("bp", [1, 16])
def test_sdar_prefill_round(sds, pallas_impl, bp):
    from dynamo_tpu.models.llama import prefill_batch

    cfg, params, kc, vc = sdar_model(sds)
    i32 = jnp.int32
    compiled = prefill_batch.lower(
        params, kc, vc, sds((bp, 512), i32), sds((bp, 65), i32),
        sds((bp,), i32), sds((bp,), i32), cfg, aligned=True).compile()
    text = compiled.as_text()
    assert text.count("kv_write_pages") >= cfg.num_layers
    assert text.count("paged_prefill_attention") >= cfg.num_layers
    assert text.count("moe_gmm") >= 3 * cfg.num_layers
    assert not re.search(r"(bf16|s8)\[128,(2048,768|768,2048)\]", text)
    # 16 x 512 tokens x top-8 = 65536 routed rows, padded to 98304, of
    # 2048 bf16 in and out of the experts: under 2.5 GB of temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < (5 << 29)


# -- Nemotron-3-Nano widths: state slots, the SSM step kernel, relu2 experts -
# What Mosaic refuses of `ssm_decode_update`, a bf16 copy of an expert stack
# or a gathered copy of the SSM state shows here and costs no chip time.

NEMOTRON = dict(vocab_size=131072, hidden_size=2688, intermediate_size=1856,
              num_heads=32, num_kv_heads=2, head_dim=128, rms_eps=1e-5,
              num_experts=128, experts_per_token=6, shared_expert_size=3712,
              mamba_heads=64, mamba_head_dim=64, ssm_groups=8, ssm_state=128,
              chunk_size=128, max_pages_per_seq=192)
NEMOTRON_LANES, NEMOTRON_SLOTS, NEMOTRON_PAGES = 128, 129, 32768
NEMOTRON_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_out", "moe_route", "moe_experts",
          "moe_shared", "moe_combine", "attn_qkv", "kv_write", "attn_core",
          "attn_out", "lm_head")


def nemotron_model(sds, pattern="MEM*E"):
    """(cfg, params, k_cache, v_cache) as shapes on the described chip:
    int8 as `--quantize int8` serves it, the expert width padded as the
    loader pads it."""
    from dynamo_tpu.engine.quant import quantize_params
    from dynamo_tpu.models import nemotron_h as nh

    cfg = nh.NemotronHConfig(num_layers=len(pattern), pattern=pattern,
                             **NEMOTRON)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    def served(key):
        params = nh.init_params(key, cfg)
        params["layers"] = nh.pad_expert_width(params["layers"])
        return quantize_params(params, mode="int8")

    params = on_chip(jax.eval_shape(served, jax.random.PRNGKey(0)))
    kc, vc = on_chip(jax.eval_shape(
        lambda: nh.init_cache(cfg, NEMOTRON_PAGES, NEMOTRON_SLOTS)))
    return cfg, params, kc, vc


def test_ssm_decode_update_kernel(sds, pallas_impl):
    from dynamo_tpu.models.nemotron_h import ssm_decode_update

    f32 = jnp.float32
    compiled = jax.jit(ssm_decode_update, donate_argnums=(0,)).lower(
        sds((NEMOTRON_SLOTS, 64, 64, 128), f32), sds((NEMOTRON_LANES,), jnp.int32),
        sds((NEMOTRON_LANES, 64, 64), f32), sds((NEMOTRON_LANES, 64), f32), sds((64,), f32),
        sds((NEMOTRON_LANES, 8, 128), f32), sds((NEMOTRON_LANES, 8, 128), f32),
        sds((64,), f32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "ssm_decode_update" in text     # the name a device trace shows
    # in place at the slots: no second copy of the state
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _kernels(text: str) -> list[tuple[str, str]]:
    """(name, scope path) of every Mosaic kernel of a compiled program."""
    return re.findall(
        r'%([\w-]+?)(?:\.\d+)* = [^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*?op_name="([^"]*)"', text)


def _no_copies(text: str) -> None:
    # no bf16 copy of an expert stack, no gathered copy of the state
    assert not re.search(r"bf16\[(1,)?128,2688,19\d\d\]", text)
    assert not re.search(r"bf16\[(1,)?128,19\d\d,2688\]", text)
    assert not re.search(rf"f32\[{NEMOTRON_LANES},64,64,128\]", text)


def test_decode_burst_holds_no_copy_of_stacks_or_state(sds, pallas_impl):
    from dynamo_tpu.models.nemotron_h import decode_multi_step

    cfg, params, kc, vc = nemotron_model(sds)
    b, i32, u32, f32 = NEMOTRON_LANES, jnp.int32, jnp.uint32, jnp.float32
    compiled = decode_multi_step.lower(
        params, kc, vc, sds((b,), i32), sds((b,), i32),
        sds((b, cfg.max_pages_per_seq), i32), sds((b,), jnp.bool_),
        sds((b,), u32), sds((b,), u32), sds((b,), f32), sds((b,), f32),
        sds((b,), i32), cfg, 8, topk_lp=0, slots=sds((b,), i32)).compile()
    text = compiled.as_text()
    assert text.count("ssm_decode_update") >= cfg.count("mamba")
    # ONE kernel a Mamba layer, under the name `ssm_update_hbm_roofline`
    # finds its seconds by, and no other kernel of the mixer beside it
    kernels = _kernels(text)
    assert [name for name, scope in kernels if "/ssm_" in scope] \
        == ["ssm_decode_update"] * cfg.count("mamba")
    assert {name for name, _ in kernels} == {
        "ssm_decode_update", "moe_gmm", "kv_write_rows",
        "paged_decode_attention", "greedy_tail"}
    assert text.count("moe_gmm") >= 2 * cfg.count("moe")
    assert text.count("paged_decode_attention") >= cfg.count("attn")
    assert not _missing(text, NEMOTRON_SCOPES + ("ssm_update", "sample"))
    _no_copies(text)
    _reads_the_heads_product(text, b, cfg.vocab_size)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_prefill_round_holds_no_copy_of_stacks(sds, pallas_impl):
    from dynamo_tpu.models.nemotron_h import prefill_batch

    cfg, params, kc, vc = nemotron_model(sds)
    i32, t = jnp.int32, 512
    compiled = prefill_batch.lower(
        params, kc, vc, sds((1, t), i32),
        sds((1, cfg.max_pages_per_seq), i32), sds((1,), i32),
        sds((1,), i32), cfg, aligned=True, slots=sds((1,), i32)).compile()
    text = compiled.as_text()
    assert text.count("moe_gmm") >= 2 * cfg.count("moe")
    assert text.count("paged_prefill_attention") >= cfg.count("attn")
    assert text.count("kv_write_pages") >= cfg.count("attn")
    assert not _missing(text, NEMOTRON_SCOPES + ("ssm_scan",))
    _no_copies(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# LFM2-8B-A1B at its published widths (benchmarks/chip/configs/
# lfm2-8b-a1b-int8.json), five layers: both operators, both FFNs
LFM2 = dict(vocab_size=65536, hidden_size=2048, intermediate_size=1792,
            dense_size=7168, num_dense_layers=2, num_heads=32,
            num_kv_heads=8, head_dim=64, rope_theta=1e6, num_experts=32,
            experts_per_token=4, max_pages_per_seq=224,
            operators=("conv", "conv", "attn", "conv", "attn"),
            num_layers=5)
LFM2_LANES, LFM2_SLOTS, LFM2_PAGES = 64, 65, 14336
LFM2_SCOPES = ("conv_in_proj", "conv_mix", "conv_out_proj", "attn_qkv",
               "kv_write", "attn_core", "attn_out", "mlp", "moe_route",
               "moe_experts", "moe_combine", "lm_head")


def lfm2_model(sds):
    """(cfg, params, k_cache, v_cache) as shapes on the described chip:
    int8 as `--quantize int8` serves it, the cache two heads a row."""
    from dynamo_tpu.engine.quant import quantize_params
    from dynamo_tpu.models import lfm2_moe as lf

    cfg = lf.Lfm2MoeConfig(**LFM2)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda k: quantize_params(lf.init_params(k, cfg), mode="int8"),
        jax.random.PRNGKey(0)))
    kc, vc = on_chip(jax.eval_shape(
        lambda: lf.init_cache(cfg, LFM2_PAGES, LFM2_SLOTS)))
    return cfg, params, kc, vc


def _no_lfm2_copies(text: str) -> None:
    # no bf16 copy of an expert stack, no cache a head a row
    assert not re.search(r"bf16\[(1,)?32,2048,1792\]", text)
    assert not re.search(r"bf16\[(1,)?32,1792,2048\]", text)
    assert not re.search(rf"bf16\[8,{LFM2_PAGES},16,64\]", text)


def test_lfm2_decode_burst_runs_the_kernels_at_head_dim_64(sds, pallas_impl):
    from dynamo_tpu.engine import attention
    from dynamo_tpu.models.lfm2_moe import decode_multi_step

    cfg, params, kc, vc = lfm2_model(sds)
    assert kc[2].shape == (4, LFM2_PAGES, 16, 128)      # 12 288 B a token
    assert kc[0].shape == (LFM2_SLOTS, 2048)
    before = attention.attention_fallbacks.get(reason="head_dim")
    b, i32, u32, f32 = LFM2_LANES, jnp.int32, jnp.uint32, jnp.float32
    compiled = decode_multi_step.lower(
        params, kc, vc, sds((b,), i32), sds((b,), i32),
        sds((b, cfg.max_pages_per_seq), i32), sds((b,), jnp.bool_),
        sds((b,), u32), sds((b,), u32), sds((b,), f32), sds((b,), f32),
        sds((b,), i32), cfg, 8, topk_lp=0, slots=sds((b,), i32)).compile()
    text = compiled.as_text()
    assert attention.attention_fallbacks.get(reason="head_dim") == before
    assert text.count("paged_decode_attention") >= cfg.count("attn")
    assert text.count("kv_write_rows") >= cfg.count("attn")
    assert text.count("moe_gmm") >= 3 * cfg.num_moe_layers
    assert not _missing(text, LFM2_SCOPES + ("sample",))
    _no_lfm2_copies(text)
    _reads_the_heads_product(text, b, cfg.vocab_size)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_lfm2_prefill_round_runs_the_kernels_at_head_dim_64(sds,
                                                            pallas_impl):
    from dynamo_tpu.engine import attention
    from dynamo_tpu.models.lfm2_moe import prefill_batch

    cfg, params, kc, vc = lfm2_model(sds)
    before = sum(attention.attention_fallbacks.get(reason=r)
                 for r in ("head_dim", "chunk_shape"))
    i32, t = jnp.int32, 512
    compiled = prefill_batch.lower(
        params, kc, vc, sds((1, t), i32),
        sds((1, cfg.max_pages_per_seq), i32), sds((1,), i32),
        sds((1,), i32), cfg, aligned=True, slots=sds((1,), i32)).compile()
    text = compiled.as_text()
    assert before == sum(attention.attention_fallbacks.get(reason=r)
                         for r in ("head_dim", "chunk_shape"))
    assert text.count("paged_prefill_attention") >= cfg.count("attn")
    assert text.count("kv_write_pages") >= cfg.count("attn")
    assert text.count("moe_gmm") >= 3 * cfg.num_moe_layers
    assert not _missing(text, LFM2_SCOPES)
    _no_lfm2_copies(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
