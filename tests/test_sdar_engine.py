"""The block-diffusion engine end to end on the toy: the same tokens at any
batch width, every page returned, a prefix-cache hit changes nothing, the
counters count what ran, and each combination that does not compose is
refused with its reason.
"""

import asyncio
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.loader import config_from_hf, load_llama_params
from dynamo_tpu.models.mixtral import MoeConfig
from tests import sdar_toy
from tests.sdar_toy import BLOCK, MASK_ID

set_attention_impl("xla")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sdar-toy"))
    sdar_toy.write_checkpoint(path)
    cfg = config_from_hf(path, dtype=jnp.float32, attn_block=BLOCK,
                         page_size=8, max_pages_per_seq=16)
    return {"path": path, "cfg": cfg,
            "params": load_llama_params(path, cfg)}


def engine_config(cfg, width=4, **kw):
    return TpuEngineConfig(
        model=cfg, num_pages=96, max_batch_size=width, prefill_chunk=32,
        decode_steps_per_sync=8, dllm_denoising_steps=4, **kw)


def requests():
    rs = np.random.RandomState(1)
    lengths = [(13, 9), (8, 12), (3, 5), (22, 16), (17, 1), (30, 24)]
    return [sdar_toy.request([int(t) for t in rs.randint(0, 290, n)], m)
            for n, m in lengths]


@pytest.fixture(scope="module")
def one_lane(toy):
    """Every request alone in a one-lane engine."""
    return [sdar_toy.serve(engine_config(toy["cfg"], width=1),
                           toy["params"], [r])[0][0] for r in requests()]


@pytest.mark.parametrize("width", [2, 4, 8])
def test_tokens_do_not_depend_on_the_batch_width(toy, one_lane, width):
    got, active = sdar_toy.serve(engine_config(toy["cfg"], width=width),
                                 toy["params"], requests())
    assert active == 0                                  # every page is back
    for (toks, lps, _, finish, error), alone, req in zip(
            got, one_lane, requests()):
        assert (finish, error) == ("length", None)
        assert len(toks) == req["stop"]["max_tokens"]
        assert toks == alone[0]
        np.testing.assert_allclose(lps, alone[1], atol=1e-5)


def test_a_prefix_cache_hit_gives_the_same_tokens(toy):
    """Pages hold whole blocks (page size a multiple of the block), so a
    page's K,V depend on nothing past its end and a hit reuses them."""
    async def run():
        engine = TpuEngine(engine_config(toy["cfg"]), params=toy["params"])
        try:
            req = requests()[5]                 # 30 tokens: 3 whole pages
            first = await sdar_toy.collect(engine, req)
            hits = []
            admit = engine._alloc_admission
            engine._alloc_admission = lambda h, n: hits.append(
                admit(h, n)) or hits[-1]
            again = await sdar_toy.collect(engine, req)
            return first, again, hits[0][1], engine.pool.active_pages
        finally:
            await engine.close()

    first, again, cached_len, active = asyncio.run(run())
    assert cached_len == 24 and active == 0
    assert first[0] == again[0]
    np.testing.assert_allclose(first[1], again[1], atol=1e-5)


def test_stop_token_inside_a_block_ends_the_stream_there(toy):
    base, _ = sdar_toy.serve(engine_config(toy["cfg"]), toy["params"],
                             [requests()[3]])
    toks = base[0][0]
    req = requests()[3]
    req["stop"] = {"max_tokens": 16, "stop_token_ids": [toks[5]],
                   "ignore_eos": True}
    got, active = sdar_toy.serve(engine_config(toy["cfg"]), toy["params"],
                                 [req])
    cut = toks.index(toks[5]) + 1
    assert got[0][0] == toks[:cut] and got[0][3] == "stop" and active == 0


def test_counters_count_forwards_blocks_and_routed_rows(toy):
    async def run():
        engine = TpuEngine(engine_config(toy["cfg"]), params=toy["params"])
        try:
            await sdar_toy.collect(engine, requests()[0])   # 13 + 9
            return engine.metrics
        finally:
            await engine.close()

    m = asyncio.run(run())
    # position 12 is given; 9 tokens end at position 21: blocks 12-15,
    # 16-19 in the first burst, 20-23 and an overshoot block in the second
    assert m.blocks.get() == 4
    assert m.block_forwards.get(kind="denoise") == 16
    assert m.block_forwards.get(kind="commit") == 4
    cfg = toy["cfg"]
    per_token = cfg.experts_per_token * cfg.num_layers
    assert m.moe_routed_rows.get() == (12 + 20 * BLOCK) * per_token
    # the burst is one program, under the decode burst's entry
    assert m.compile.compile_total.get(entry="decode_burst",
                                       shape="4x8x4x4") == 1


REFUSED_AT_START = [
    (dict(draft_model=LlamaConfig.tiny()), "draft model"),
    (dict(prefill_chunk_budget=64), "prefill_chunk_budget"),
    (dict(dllm_denoising_steps=3), "must divide the block"),
    (dict(dllm_unmasking_strategy="low_confidence_dynamic"),
     "dllm_unmasking_strategy"),
    (dict(decode_steps_per_sync=6), "multiple of the block"),
]


@pytest.mark.parametrize("kw,message", REFUSED_AT_START,
                         ids=[m for _, m in REFUSED_AT_START])
def test_refused_at_start(toy, kw, message):
    config = dataclasses.replace(engine_config(toy["cfg"]), **kw)
    with pytest.raises(ValueError, match=message):
        TpuEngine(config, params=toy["params"])


def test_pp_and_sp_meshes_are_refused(toy, cpu_mesh_devices):
    import jax

    mesh = jax.sharding.Mesh(np.asarray(cpu_mesh_devices[:2]), ("pp",))
    dense = dataclasses.replace(
        LlamaConfig.tiny(), attn_block=BLOCK, mask_token_id=7,
        dtype=jnp.float32)
    for kw in (dict(pp_mesh=mesh),
               dict(sp_mesh=jax.sharding.Mesh(
                   np.asarray(cpu_mesh_devices[:2]), ("sp",)),
                   sp_threshold=8)):
        with pytest.raises(ValueError, match="pp / sp meshes"):
            TpuEngine(TpuEngineConfig(model=dense, dllm_denoising_steps=4,
                                      **kw))


def test_the_ragged_path_is_refused(toy):
    set_attention_impl("ragged")
    try:
        with pytest.raises(ValueError, match="ragged"):
            TpuEngine(engine_config(toy["cfg"]), params=toy["params"])
    finally:
        set_attention_impl("xla")


def test_a_mask_id_outside_the_vocabulary_is_refused(toy):
    cfg = dataclasses.replace(toy["cfg"], mask_token_id=-1)
    with pytest.raises(ValueError, match="mask_token_id"):
        TpuEngine(engine_config(cfg), params=toy["params"])


def test_a_page_size_that_splits_blocks_is_refused():
    cfg = MoeConfig.tiny(attn_block=8, mask_token_id=3, page_size=4)
    with pytest.raises(ValueError, match="page size 4"):
        TpuEngine(TpuEngineConfig(model=cfg, dllm_denoising_steps=4))


REFUSED_REQUESTS = [
    ({"guided": {"regex": "[a-z]+"}}, "guided decoding"),
    ({"top_logprobs": 3}, "top_logprobs alternatives"),
    ({"min_p": 0.2}, "min_p and sampling penalties"),
    ({"presence_penalty": 0.5}, "min_p and sampling penalties"),
]


@pytest.mark.parametrize("sampling,message", REFUSED_REQUESTS,
                         ids=[str(list(s)[0]) for s, _ in REFUSED_REQUESTS])
def test_refused_per_request(toy, sampling, message):
    good = requests()[2]
    bad = sdar_toy.request(good["token_ids"], 5, **sampling)
    got, active = sdar_toy.serve(engine_config(toy["cfg"]), toy["params"],
                                 [bad, good])
    assert got[0][3] == "error" and message in got[0][4]
    assert got[0][0] == []
    # the engine goes on serving: `logprobs: 0` (the chosen token's
    # log-probability, no alternatives) is what the probe asks for
    assert got[1][3] == "length" and len(got[1][1]) == 5 and active == 0


def test_a_diffusion_checkpoint_does_not_start_without_its_block_length(toy):
    from dynamo_tpu.llm.entrypoint import build_tpu_engine

    with pytest.raises(ValueError, match="--dllm-block-length"):
        build_tpu_engine(toy["path"])
    engine, _ = build_tpu_engine(toy["path"], attn_block=BLOCK,
                                 dllm_denoising_steps=4, num_pages=64,
                                 max_pages_per_seq=8)
    assert engine._dllm and engine.model_cfg.mask_token_id == MASK_ID


def test_worker_flags_reach_the_engine_config():
    from dynamo_tpu.worker.main import parse_args

    args = parse_args(["--model", "x", "--dllm-block-length", "4",
                       "--dllm-unmasking-strategy",
                       "low_confidence_static"])
    assert (args.dllm_block_length, args.dllm_denoising_steps,
            args.dllm_unmasking_strategy) == (4, 0, "low_confidence_static")
