"""The sampler builds its candidate set (a top-k over the vocabulary a row)
only where some lane of the batch draws: a greedy batch is an argmax, a
batch with a drawing lane is, id for id, the function as it stood before
the condition (kept below as `sample_before`), every fused step holds its
top-k inside one conditional a sampling site, and the engine counts the
dispatches whose sampler went greedy. Since PR 49 every site takes its
token AND its log-probability from `sample_with_logprob`: the pair is
`(sample_tokens_traced, chosen_logprob)`, and the greedy branch holds no
log-softmax over the vocabulary.
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
from dynamo_tpu.models import lfm2_moe, llama, llama_pp, nemotron_h
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


def _pair(logits, seeds, steps, temperature, top_p, top_k, min_p=None,
          **kw):
    return [np.asarray(a) for a in jax.jit(
        lambda lg: sampling.sample_with_logprob(
            lg, seeds, steps, temperature, top_p, top_k, min_p, **kw))(
        logits)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("b,v", [(1, 97), (4, 300), (16, 4096)])
def test_a_greedy_pair_is_the_argmax_and_its_chosen_logprob(b, v, dtype):
    """logits as the head hands them on, widened by the sampler: the pair
    is `(sample_tokens_traced, chosen_logprob)` of the widened logits."""
    logits, seeds, steps = lanes(b, v, seed=b, step=3)
    logits = logits.astype(dtype)
    knobs = (jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
             jnp.zeros((b,), jnp.int32))
    tok, lp, best = _pair(logits, seeds, steps, *knobs, best=True)
    wide = logits.astype(jnp.float32)
    want = sampling.sample_tokens_traced(wide, seeds, steps, *knobs)
    np.testing.assert_array_equal(tok, want)
    np.testing.assert_allclose(lp, sampling.chosen_logprob(wide, want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(best, lp)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", sorted(FILTERS))
def test_a_mixed_pair_is_the_function_as_it_stood(name, seed):
    """`sample_with_logprob` itself on the mixed batches above, and
    through `rows` (a taller array the lanes read out of order): ids bit
    for bit, log-probabilities to 1e-6, the confidence max(log_softmax)."""
    b, v = 8, 512
    f = FILTERS[name]
    temperature = jnp.asarray([0.0, 0.7, 0.0, 1.0, 2.0, 0.0, 1.3, 0.0],
                              jnp.float32)
    top_p = jnp.full((b,), f["top_p"], jnp.float32)
    top_k = jnp.full((b,), f["top_k"], jnp.int32)
    min_p = (None if f["min_p"] is None
             else jnp.full((b,), f["min_p"], jnp.float32))
    rows = jnp.asarray([9, 2, 2, 0, 7, 5, 11, 1], jnp.int32)
    for step in range(3):
        tall, _, _ = lanes(12, v, seed=23 * seed + step, step=step)
        _, seeds, steps = lanes(b, v, seed=17 * seed + step, step=step)
        want = np.asarray(lp_before(tall[rows], seeds, steps, temperature,
                                    top_p, top_k, min_p))
        for got in (_pair(tall[rows], seeds, steps, temperature, top_p,
                          top_k, min_p, best=True),
                    _pair(tall, seeds, steps, temperature, top_p, top_k,
                          min_p, rows=rows, best=True)):
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_allclose(got[1], want[1], rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(
                got[2], np.max(jax.nn.log_softmax(tall[rows]), axis=-1),
                rtol=1e-6, atol=1e-6)


def test_a_greedy_batch_reads_the_rows_there_are():
    """`rows` on a greedy batch: the rows of the shorter array reduced,
    two numbers a lane gathered."""
    tall, seeds, steps = lanes(3, 640, seed=5, step=0)
    rows = jnp.asarray([2, 0, 0, 1, 2, 2, 1, 0], jnp.int32)
    z = jnp.zeros((8,), jnp.float32)
    knobs = (jnp.zeros((8,), jnp.uint32), jnp.zeros((8,), jnp.int32), z,
             z + 1.0, jnp.zeros((8,), jnp.int32))
    tok, lp = _pair(tall, *knobs, rows=rows)
    want_tok, want_lp = _pair(tall[rows], *knobs)
    np.testing.assert_array_equal(tok, want_tok)
    np.testing.assert_array_equal(lp, want_lp)
    np.testing.assert_array_equal(tok, np.argmax(tall[rows], axis=-1))


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


def count_over(jaxpr, width, names):
    """How many operations named in `names` take or give an array of
    `width` columns, in `jaxpr` and everything nested in it."""
    n = 0
    for eqn in jaxpr.eqns:
        shapes = [getattr(v.aval, "shape", ()) for v in
                  list(eqn.invars) + list(eqn.outvars)]
        if eqn.primitive.name in names and any(
                sh and sh[-1] == width for sh in shapes):
            n += 1
        for sub in _subjaxprs(eqn):
            n += count_over(sub, width, names)
    return n


def sampling_conds(jaxpr, width):
    """[(greedy branch, drawing branch)] of every `cond` that holds a
    top-k over `width` columns in one branch and none in the other."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            branches = [b.jaxpr for b in eqn.params["branches"]]
            holds = [bool(top_k_sites(b, width)) for b in branches]
            if any(holds) and not all(holds):
                found.append((branches[holds.index(False)],
                              branches[holds.index(True)]))
                continue
        for sub in _subjaxprs(eqn):
            found += sampling_conds(sub, width)
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


def _trace_lfm2():
    from tests.lfm2_toy import CONFIG

    cfg = lfm2_moe.config_from_hf(CONFIG)
    x = _lane_inputs(2, cfg)
    kc, vc = lfm2_moe.init_cache(cfg, 8, 3)
    return jax.make_jaxpr(
        lambda p, k, v: lfm2_moe.decode_multi_step(
            p, k, v, x["tokens"], x["positions"], x["page_tables"],
            x["valid"], x["seeds"], x["steps"], x["temperature"],
            x["top_p"], x["top_k"], cfg, 2,
            slots=jnp.asarray([1, 2], jnp.int32)))(
        lfm2_moe.init_params(jax.random.PRNGKey(0), cfg), kc, vc
    ), cfg.vocab_size


def _greedy(x):
    return (x["seeds"], x["steps"], x["temperature"], x["top_p"], x["top_k"])


def _trace_mixed():
    cfg = LlamaConfig.tiny()
    x = _lane_inputs(2, cfg)
    kc, vc = llama.init_cache(cfg, 8)
    one = jnp.zeros((1,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, k, v: llama.mixed_prefill_decode(
            p, k, v, jnp.zeros((1, 16), jnp.int32), x["page_tables"][:1],
            one, one + 16, x["tokens"], x["positions"], x["page_tables"],
            x["valid"], *_greedy(x), cfg, 3))(
        llama.init_params(jax.random.PRNGKey(0), cfg), kc, vc), cfg.vocab_size


def _trace_ragged():
    cfg = LlamaConfig.tiny()
    x = _lane_inputs(2, cfg)
    kc, vc = llama.init_cache(cfg, 8)
    flat = jnp.zeros((8,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, k, v: llama.ragged_prefill_decode(
            p, k, v, flat, flat, flat, flat, flat > 0, flat,
            x["page_tables"], x["tokens"], x["tokens"], *_greedy(x), cfg))(
        llama.init_params(jax.random.PRNGKey(0), cfg), kc, vc), cfg.vocab_size


def _guided_inputs(b, v):
    f32 = jnp.float32
    return dict(
        min_p=jnp.zeros((b,), f32), rep_pen=jnp.ones((b,), f32),
        freq_pen=jnp.zeros((b,), f32), pres_pen=jnp.zeros((b,), f32),
        prompt_counts=jnp.zeros((b, v), jnp.int32),
        out_counts=jnp.zeros((b, v), jnp.int32),
        g_bits=jnp.full((1, 1, -(-v // 8)), 255, jnp.uint8),
        g_next=jnp.zeros((1, 1, v), jnp.int16),
        g_eos_ok=jnp.ones((1, 1), bool), g_ids=jnp.zeros((b,), jnp.int32),
        g_states=jnp.zeros((b,), jnp.int32),
        stop_ids=jnp.full((b, 1), -1, jnp.int32))


def _trace_guided():
    cfg = LlamaConfig.tiny()
    x = _lane_inputs(2, cfg)
    kc, vc = llama.init_cache(cfg, 8)
    return jax.make_jaxpr(
        lambda p, k, v: llama.decode_multi_step_guided(
            p, k, v, x["tokens"], x["positions"], x["page_tables"],
            x["valid"], *_greedy(x),
            *_guided_inputs(2, cfg.vocab_size).values(), cfg, 2))(
        llama.init_params(jax.random.PRNGKey(0), cfg), kc, vc), cfg.vocab_size


def _trace_pp():
    from jax.sharding import Mesh

    from dynamo_tpu.engine.pages import kv_layer_shape

    cfg = LlamaConfig.tiny()
    x = _lane_inputs(2, cfg)
    mesh = Mesh(np.asarray(jax.devices("cpu")[:2]), axis_names=("pp",))
    stacked = jnp.zeros((cfg.num_layers,) + kv_layer_shape(cfg, 8),
                        cfg.dtype)
    return jax.make_jaxpr(
        lambda p, k, v: llama_pp.pp_decode_multi_step(
            p, k, v, x["tokens"], x["positions"], x["page_tables"],
            x["valid"], *_greedy(x), cfg, mesh, 2, n_micro=2))(
        llama.init_params(jax.random.PRNGKey(0), cfg), stacked, stacked
    ), cfg.vocab_size


def _trace_block(strategy="sequential"):
    cfg = LlamaConfig.tiny(attn_block=4, mask_token_id=5)
    x = _lane_inputs(2, cfg)
    kc, vc = llama.init_cache(cfg, 8)
    return jax.make_jaxpr(
        lambda p, k, v: llama.block_decode_multi_step(
            p, k, v, jnp.zeros((2, 4), jnp.int32), x["tokens"],
            x["positions"], x["page_tables"], x["valid"], x["seeds"],
            x["temperature"], x["top_p"], x["top_k"], cfg, 2, 4,
            strategy))(
        llama.init_params(jax.random.PRNGKey(0), cfg), kc, vc), cfg.vocab_size


def _trace_sampler():
    logits, seeds, steps = lanes(4, 128, seed=0, step=0)
    z = jnp.zeros((4,), jnp.float32)
    return jax.make_jaxpr(
        lambda lg: sampling.sample_tokens_lp(
            lg, seeds, steps, z, z + 1.0, jnp.zeros((4,), jnp.int32)))(
        logits), 128


# every program that samples inside itself, with the sampling sites it has
# (the mixed step samples its step 0 outside the loop and the rest inside)
PROGRAMS = {
    "llama.decode_multi_step": (_trace_llama, 1),
    "nemotron_h.decode_multi_step": (_trace_nemotron, 1),
    "lfm2_moe.decode_multi_step": (_trace_lfm2, 1),
    "llama.block_decode_multi_step": (_trace_block, 1),
    "llama.block_decode_multi_step.low_confidence": (
        lambda: _trace_block("low_confidence_static"), 1),
    "llama.mixed_prefill_decode": (_trace_mixed, 2),
    "llama.ragged_prefill_decode": (_trace_ragged, 1),
    "llama.decode_multi_step_guided": (_trace_guided, 1),
    "llama_pp.pp_decode_multi_step": (_trace_pp, 1),
    "sample_tokens_lp": (_trace_sampler, 1),
}


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_the_top_k_sits_inside_one_conditional_a_site(name):
    """Each of these programs asks for no top-k alternatives: one `top_k`
    a sampling site, under exactly one `cond` (the block burst kept a
    condition of its own around the call until the sampler took it over:
    two nested would read 2). That `cond` is `sample_with_logprob`'s: it
    hands on the pair (the block burst under `low_confidence_static` a
    third, its confidence), and its greedy branch holds no log-softmax
    over the vocabulary: no row maximum, one subtraction (x - x[argmax])
    where a log-softmax has two, and nothing the drawing branch has."""
    trace, sites = PROGRAMS[name]
    program, vocabulary = trace()
    assert top_k_sites(program.jaxpr, vocabulary) == [1] * sites
    conds = sampling_conds(program.jaxpr, vocabulary)
    assert len(conds) == sites
    for greedy, drawing in conds:
        assert len(greedy.outvars) == (3 if "low_confidence" in name else 2)
        assert count_over(greedy, vocabulary, {"reduce_max"}) == 0
        assert count_over(greedy, vocabulary, {"sub"}) == 1
        assert count_over(greedy, vocabulary, {"argmax"}) == 1
        # the drawing branch is the function as it stood: its log-softmax
        # (a row maximum of its own) is there
        assert count_over(drawing, vocabulary, {"reduce_max"}) >= 1


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
