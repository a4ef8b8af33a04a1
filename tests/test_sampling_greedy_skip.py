"""The sampler builds its candidate set (a top-k over the vocabulary a row)
only where some lane of the batch draws: a greedy batch is an argmax, a
batch with a drawing lane is, id for id, the function as it stood before
the condition (kept below as `sample_before`), every fused step holds its
top-k inside one conditional a sampling site, and the engine counts the
dispatches whose sampler went greedy.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dynamo_tpu.engine import sampling
from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models import llama, nemotron_h
from dynamo_tpu.models.llama import LlamaConfig
from tests.sdar_toy import collect, request

set_attention_impl("xla")


def sample_before(logits, seeds, steps, temperature, top_p, top_k,
                  min_p=None):
    """`sample_tokens_traced` as it stood before the condition."""
    greedy = jnp.argmax(logits, axis=-1)
    masked, cand_idx, t = sampling._candidate_mask(
        logits, temperature, top_p, top_k, min_p)

    def sample_one(seed, step, lg, tt):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.categorical(key, lg / tt)

    choice = jax.vmap(sample_one)(
        seeds.astype(jnp.uint32), steps.astype(jnp.uint32), masked, t)
    sampled = jnp.take_along_axis(cand_idx, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


@jax.jit
def lp_before(logits, seeds, steps, temperature, top_p, top_k, min_p):
    sampled = sample_before(logits, seeds, steps, temperature, top_p, top_k,
                            min_p)
    return jnp.stack([sampled.astype(jnp.float32),
                      sampling.chosen_logprob(logits, sampled)])


def lanes(b, v, seed, step):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(b, v).astype(np.float32) * 3.0),
            jnp.asarray(rs.randint(0, 2**31, b).astype(np.uint32)),
            jnp.full((b,), step, jnp.int32))


@pytest.mark.parametrize("b,v", [(1, 97), (4, 300), (16, 4096)])
def test_a_greedy_batch_is_the_argmax(b, v):
    logits, seeds, steps = lanes(b, v, seed=b, step=3)
    knobs = (jnp.zeros((b,), jnp.float32), jnp.full((b,), 0.9, jnp.float32),
             jnp.full((b,), 5, jnp.int32), jnp.full((b,), 0.1, jnp.float32))
    got = np.asarray(sampling.sample_tokens_lp(logits, seeds, steps, *knobs))
    want = np.asarray(lp_before(logits, seeds, steps, *knobs))
    np.testing.assert_array_equal(got[0], np.argmax(logits, axis=-1))
    np.testing.assert_array_equal(got, want)        # ids and chosen_logprob


FILTERS = {
    "plain": dict(top_p=1.0, top_k=0, min_p=None),
    "top_k": dict(top_p=1.0, top_k=7, min_p=None),
    "top_p": dict(top_p=0.8, top_k=0, min_p=None),
    "min_p": dict(top_p=1.0, top_k=0, min_p=0.2),
    "all": dict(top_p=0.9, top_k=20, min_p=0.05),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", sorted(FILTERS))
def test_a_mixed_batch_is_the_function_as_it_stood(name, seed):
    """Some lanes at temperature 0, the others drawing: the same ids, and
    a greedy lane's id the argmax, over several steps."""
    b, v = 8, 512
    f = FILTERS[name]
    temperature = jnp.asarray([0.0, 0.7, 0.0, 1.0, 2.0, 0.0, 1.3, 0.0],
                              jnp.float32)
    top_p = jnp.full((b,), f["top_p"], jnp.float32)
    top_k = jnp.full((b,), f["top_k"], jnp.int32)
    min_p = (None if f["min_p"] is None
             else jnp.full((b,), f["min_p"], jnp.float32))
    drew = False
    for step in range(4):
        logits, seeds, steps = lanes(b, v, seed=17 * seed + step, step=step)
        got = np.asarray(sampling.sample_tokens_lp(
            logits, seeds, steps, temperature, top_p, top_k, min_p))
        want = np.asarray(lp_before(logits, seeds, steps, temperature,
                                    top_p, top_k, min_p))
        np.testing.assert_array_equal(got, want)
        best = np.argmax(logits, axis=-1)
        cold = np.asarray(temperature) == 0
        np.testing.assert_array_equal(got[0][cold], best[cold])
        drew |= bool((got[0][~cold] != best[~cold]).any())
    assert drew                                 # the drawing lanes did draw


# -- where the top-k sits in each program ------------------------------------

def _subjaxprs(eqn):
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            if hasattr(item, "jaxpr"):            # ClosedJaxpr
                yield item.jaxpr
            elif hasattr(item, "eqns"):           # Jaxpr
                yield item


def top_k_sites(jaxpr, width, conds=0):
    """[the number of `cond`s around each `top_k` over rows of `width`
    columns (the vocabulary: an expert router's top-k is another width)]"""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "top_k"
                and eqn.invars[0].aval.shape[-1] == width):
            found.append(conds)
        inner = conds + (eqn.primitive.name == "cond")
        for sub in _subjaxprs(eqn):
            found += top_k_sites(sub, width, inner)
    return found


def _lane_inputs(b, cfg):
    i32, f32 = jnp.int32, jnp.float32
    return dict(
        tokens=jnp.zeros((b,), i32), positions=jnp.zeros((b,), i32),
        page_tables=jnp.zeros((b, cfg.max_pages_per_seq), i32),
        valid=jnp.ones((b,), bool), seeds=jnp.zeros((b,), jnp.uint32),
        steps=jnp.zeros((b,), i32), temperature=jnp.zeros((b,), f32),
        top_p=jnp.ones((b,), f32), top_k=jnp.zeros((b,), i32))


def _trace_llama():
    cfg = LlamaConfig.tiny()
    x = _lane_inputs(2, cfg)
    kc, vc = llama.init_cache(cfg, 8)
    return jax.make_jaxpr(
        lambda p, k, v: llama.decode_multi_step(
            p, k, v, x["tokens"], x["positions"], x["page_tables"],
            x["valid"], x["seeds"], x["steps"], x["temperature"],
            x["top_p"], x["top_k"], cfg, 2))(
        llama.init_params(jax.random.PRNGKey(0), cfg), kc, vc), cfg.vocab_size


def _trace_nemotron():
    cfg = nemotron_h.NemotronHConfig.tiny()
    x = _lane_inputs(2, cfg)
    kc, vc = nemotron_h.init_cache(cfg, 8, 3)
    return jax.make_jaxpr(
        lambda p, k, v: nemotron_h.decode_multi_step(
            p, k, v, x["tokens"], x["positions"], x["page_tables"],
            x["valid"], x["seeds"], x["steps"], x["temperature"],
            x["top_p"], x["top_k"], cfg, 2,
            slots=jnp.asarray([1, 2], jnp.int32)))(
        nemotron_h.init_params(jax.random.PRNGKey(0), cfg), kc, vc
    ), cfg.vocab_size


def _trace_block():
    cfg = LlamaConfig.tiny(attn_block=4, mask_token_id=5)
    x = _lane_inputs(2, cfg)
    kc, vc = llama.init_cache(cfg, 8)
    return jax.make_jaxpr(
        lambda p, k, v: llama.block_decode_multi_step(
            p, k, v, jnp.zeros((2, 4), jnp.int32), x["tokens"],
            x["positions"], x["page_tables"], x["valid"], x["seeds"],
            x["temperature"], x["top_p"], x["top_k"], cfg, 2, 4,
            "sequential"))(
        llama.init_params(jax.random.PRNGKey(0), cfg), kc, vc), cfg.vocab_size


def _trace_sampler():
    logits, seeds, steps = lanes(4, 128, seed=0, step=0)
    z = jnp.zeros((4,), jnp.float32)
    return jax.make_jaxpr(
        lambda lg: sampling.sample_tokens_lp(
            lg, seeds, steps, z, z + 1.0, jnp.zeros((4,), jnp.int32)))(
        logits), 128


@pytest.mark.parametrize("trace", [_trace_llama, _trace_nemotron,
                                   _trace_block, _trace_sampler],
                         ids=["llama.decode_multi_step",
                              "nemotron_h.decode_multi_step",
                              "llama.block_decode_multi_step",
                              "sample_tokens_lp"])
def test_the_top_k_sits_inside_one_conditional_a_site(trace):
    """Each of these programs has one sampling site and asks for no top-k
    alternatives: one `top_k`, under exactly one `cond` (the block burst
    kept a condition of its own around the call until the sampler took
    it over: two nested would read 2)."""
    program, vocabulary = trace()
    assert top_k_sites(program.jaxpr, vocabulary) == [1]


def test_the_walk_sees_a_bare_and_a_nested_top_k():
    def bare(x):
        return lax.top_k(x, 2)[0]

    def nested(x):
        return lax.cond(
            x[0] > 0,
            lambda: lax.cond(x[1] > 0, lambda: bare(x), lambda: x[:2]),
            lambda: x[:2])

    x = jnp.arange(4.0)
    assert top_k_sites(jax.make_jaxpr(bare)(x).jaxpr, 4) == [0]
    assert top_k_sites(jax.make_jaxpr(bare)(x).jaxpr, 5) == []
    assert top_k_sites(jax.make_jaxpr(jax.jit(nested))(x).jaxpr, 4) == [2]


# -- the counter --------------------------------------------------------------

def _serve(temperature):
    cfg = LlamaConfig.tiny()

    async def run():
        engine = TpuEngine(TpuEngineConfig(
            model=cfg, num_pages=32, max_batch_size=2,
            decode_steps_per_sync=4))
        try:
            await collect(engine, request(
                [3, 4, 5, 6, 7], 9, temperature=temperature, seed=5))
            c = engine.metrics.sampler_greedy_dispatches
            return {lab["entry"]: int(v) for lab, v in c.items()}
        finally:
            await engine.close()

    return asyncio.run(run())


def test_the_counter_moves_on_a_greedy_batch():
    counts = _serve(0.0)
    assert counts["sample_first"] == 1      # the first token's sampler
    assert counts["decode_burst"] >= 2      # 8 more tokens, 4 a burst


def test_the_counter_stands_still_on_a_drawing_batch():
    assert _serve(0.8) == {}
