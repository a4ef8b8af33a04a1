"""Speculative decoding (engine/spec.py + engine integration).

The load-bearing property: GREEDY spec output equals the target-only
greedy sequence for ANY draft — accepted tokens pass the argmax-equality
test and the extra token is itself a target argmax, so the draft only
changes HOW FAST tokens come out, never WHICH tokens. That makes
"random draft, greedy, compare against no-draft engine" the strongest
rollback/cache-garbage test available.
"""

import asyncio

import jax
import numpy as np

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig, init_params
from dynamo_tpu.runtime.context import Context

set_attention_impl("xla")

CFG = LlamaConfig.tiny()
PROMPT = [1, 2, 3, 4, 5, 6, 7]


async def run_engine(draft_params=None, draft_cfg=None, temperature=0.0,
                     top_p=1.0, n_tokens=24, metrics=None):
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=96, max_batch_size=2,
        default_max_tokens=n_tokens, decode_steps_per_sync=4,
        draft_model=draft_cfg, spec_gamma=3, spec_iters_per_sync=2),
        draft_params=draft_params, metrics_sink=metrics)
    req = {"token_ids": list(PROMPT), "model": "m",
           "sampling": {"temperature": temperature, "top_p": top_p},
           "stop": {"max_tokens": n_tokens}}
    toks = []
    async for o in eng.generate(req, Context()):
        toks += o.get("token_ids", [])
    stats = eng._spec_stats
    await eng.close()
    return toks, stats


async def test_greedy_spec_with_random_draft_matches_target_only():
    base, _ = await run_engine()
    # a draft with DIFFERENT weights: low acceptance, same greedy output
    draft_params = init_params(jax.random.PRNGKey(99), CFG)
    spec, stats = await run_engine(draft_params=draft_params, draft_cfg=CFG)
    assert spec == base
    assert stats.num_draft_tokens > 0


async def test_greedy_spec_with_self_draft_accepts_everything():
    base, _ = await run_engine()
    # draft == target: every proposal verifies (modulo bf16 near-ties
    # between the decode and verify attention paths)
    target_params = init_params(jax.random.PRNGKey(0), CFG)
    spec, stats = await run_engine(draft_params=target_params,
                                   draft_cfg=CFG)
    assert spec == base
    assert stats.acceptance_rate > 0.8, stats.to_dict()


async def test_stochastic_spec_self_draft_high_acceptance():
    target_params = init_params(jax.random.PRNGKey(0), CFG)
    toks, stats = await run_engine(draft_params=target_params,
                                   draft_cfg=CFG, temperature=0.8)
    assert len(toks) == 24
    # p_t == p_d ⇒ the ratio test accepts with probability ~1
    assert stats.acceptance_rate > 0.8, stats.to_dict()


async def test_nucleus_lane_rides_spec_bursts():
    # the rejection test runs on the FILTERED distribution, so nucleus
    # lanes no longer fall back; with draft == target the filtered dists
    # are identical and acceptance stays high
    target_params = init_params(jax.random.PRNGKey(0), CFG)
    toks, stats = await run_engine(draft_params=target_params,
                                   draft_cfg=CFG,
                                   temperature=0.8, top_p=0.5)
    assert len(toks) == 24
    assert stats.num_draft_tokens > 0
    assert stats.acceptance_rate > 0.8, stats.to_dict()


async def test_min_p_lane_rides_spec_bursts():
    # min_p threads through filtered_probs on BOTH the draft and target
    # sides (r4 excluded these lanes; now they speculate)
    target_params = init_params(jax.random.PRNGKey(0), CFG)
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=96, max_batch_size=2, default_max_tokens=8,
        draft_model=CFG, spec_gamma=2, spec_iters_per_sync=2),
        params=target_params, draft_params=target_params)
    req = {"token_ids": list(PROMPT), "model": "m",
           "sampling": {"temperature": 0.8, "min_p": 0.2, "seed": 3},
           "stop": {"max_tokens": 8}}
    toks = [t async for o in eng.generate(req, Context())
            for t in o.get("token_ids", [])]
    assert len(toks) == 8
    st = eng._spec_stats
    assert st.num_draft_tokens > 0, "min_p lane must keep speculation"
    # self-draft + identical filters ⇒ acceptance stays high
    assert st.acceptance_rate > 0.8, st.to_dict()
    await eng.close()


async def test_greedy_penalty_spec_matches_constrained_engine():
    """Greedy + repetition/frequency/presence penalties through a spec
    burst must emit EXACTLY the no-draft constrained engine's tokens —
    the tentative-counts chain makes the verify distribution at every
    position identical to the sequential constrained one."""
    sampling = {"temperature": 0.0, "repetition_penalty": 1.3,
                "frequency_penalty": 0.2, "presence_penalty": 0.1}

    async def run(draft):
        eng = TpuEngine(TpuEngineConfig(
            model=CFG, num_pages=96, max_batch_size=2,
            default_max_tokens=24, decode_steps_per_sync=4,
            draft_model=CFG if draft else None, spec_gamma=3,
            spec_iters_per_sync=2),
            draft_params=(init_params(jax.random.PRNGKey(99), CFG)
                          if draft else None))
        req = {"token_ids": list(PROMPT), "model": "m",
               "sampling": dict(sampling), "stop": {"max_tokens": 24}}
        toks = []
        async for o in eng.generate(req, Context()):
            toks += o.get("token_ids", [])
        stats = eng._spec_stats
        await eng.close()
        return toks, stats

    base, _ = await run(draft=False)
    spec, stats = await run(draft=True)
    assert spec == base
    assert stats.num_draft_tokens > 0, \
        "penalty lane must keep speculation"


async def test_spec_penalty_mixed_batch_with_plain_lane():
    """A penalty lane and a plain greedy lane share one spec burst;
    the plain lane's output must equal its solo greedy sequence."""
    base, _ = await run_engine(n_tokens=16)
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=96, max_batch_size=2, default_max_tokens=16,
        decode_steps_per_sync=4, draft_model=CFG, spec_gamma=3,
        spec_iters_per_sync=2),
        draft_params=init_params(jax.random.PRNGKey(0), CFG))

    async def plain():
        req = {"token_ids": list(PROMPT), "model": "m",
               "sampling": {"temperature": 0.0},
               "stop": {"max_tokens": 16}}
        return [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", [])]

    async def penalized():
        req = {"token_ids": [9, 8, 7], "model": "m",
               "sampling": {"temperature": 0.7, "seed": 11,
                            "repetition_penalty": 1.2,
                            "frequency_penalty": 0.3},
               "stop": {"max_tokens": 12}}
        return [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", [])]

    p, q = await asyncio.gather(plain(), penalized())
    assert p == base
    assert len(q) == 12
    assert eng._spec_stats.num_draft_tokens > 0
    await eng.close()


async def test_spec_output_deterministic():
    draft_params = init_params(jax.random.PRNGKey(99), CFG)
    a, _ = await run_engine(draft_params=draft_params, draft_cfg=CFG,
                            temperature=0.7)
    b, _ = await run_engine(draft_params=draft_params, draft_cfg=CFG,
                            temperature=0.7)
    assert a == b and len(a) == 24


async def test_spec_with_quantized_engine():
    draft_params = init_params(jax.random.PRNGKey(99), CFG)
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=96, max_batch_size=2, default_max_tokens=8,
        draft_model=CFG, spec_gamma=2, spec_iters_per_sync=2,
        quantize="int8"), draft_params=draft_params)
    req = {"token_ids": list(PROMPT), "model": "m",
           "sampling": {"temperature": 0.0}, "stop": {"max_tokens": 8}}
    toks = []
    async for o in eng.generate(req, Context()):
        toks += o.get("token_ids", [])
    assert len(toks) == 8
    await eng.close()


def test_spec_geometry_mismatch_rejected():
    import pytest

    bad = LlamaConfig.tiny(page_size=8)
    with pytest.raises(ValueError):
        TpuEngine(TpuEngineConfig(model=CFG, draft_model=bad))


async def test_near_max_context_spec_does_not_overflow_page_table():
    # spec lookahead (spec_iters*(gamma+1)=24) > decode_steps_per_sync:
    # the admission guard must budget the spec shape, and an admitted
    # request at the boundary must decode without overflowing
    # max_pages_per_seq (r2 review finding)
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=96, max_batch_size=1, default_max_tokens=8,
        decode_steps_per_sync=4, draft_model=CFG, spec_gamma=3,
        spec_iters_per_sync=6))
    ctx_len = CFG.page_size * CFG.max_pages_per_seq  # 64
    lookahead = 6 * 4
    prompt_len = ctx_len - lookahead - 8              # max admissible
    req = {"token_ids": [(i % 250) + 1 for i in range(prompt_len)],
           "model": "m", "sampling": {"temperature": 0.0},
           "stop": {"max_tokens": 8}}
    outs = [o async for o in eng.generate(dict(req), Context())]
    assert outs[-1].get("finish_reason") == "length", outs[-1]
    # one token longer must be refused, not crash mid-decode
    req["token_ids"].append(1)
    outs = [o async for o in eng.generate(dict(req), Context())]
    assert outs[-1].get("finish_reason") == "error"
    await eng.close()


async def test_draft_catchup_after_fallback_burst():
    # lane A (greedy) decodes alongside lane B (nucleus) ⇒ the batch is
    # spec-incompatible and A's tokens come from FALLBACK bursts with no
    # draft KV. When B finishes, A's next spec burst must replay those
    # tokens through the draft (engine._draft_catchup) — output must
    # still equal the target-only greedy sequence (r2 review finding)
    base, _ = await run_engine(n_tokens=40)

    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=96, max_batch_size=2, default_max_tokens=40,
        decode_steps_per_sync=4, draft_model=CFG, spec_gamma=3,
        spec_iters_per_sync=2),
        draft_params=init_params(jax.random.PRNGKey(0), CFG))

    async def greedy():
        req = {"token_ids": list(PROMPT), "model": "m",
               "sampling": {"temperature": 0.0},
               "stop": {"max_tokens": 40}}
        return [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", [])]

    async def nucleus():
        req = {"token_ids": [9, 8, 7], "model": "m",
               "sampling": {"temperature": 0.9, "top_p": 0.5},
               "stop": {"max_tokens": 6}}
        return [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", [])]

    toks_a, toks_b = await asyncio.gather(greedy(), nucleus())
    assert len(toks_b) == 6
    assert toks_a == base
    # the spec path DID engage after the nucleus lane drained
    assert eng._spec_stats.num_draft_tokens > 0
    await eng.close()


async def test_greedy_spec_with_guided_matches_constrained_engine():
    """Constrained lanes coexist in a spec burst. Greedy
    spec+grammar output must equal the no-draft constrained engine's —
    the draft only changes speed, never tokens, even under a mask."""
    token_bytes = [bytes([i]) if i < 256 else None
                   for i in range(CFG.vocab_size)]

    async def run(draft):
        eng = TpuEngine(TpuEngineConfig(
            model=CFG, num_pages=96, max_batch_size=2,
            default_max_tokens=12, decode_steps_per_sync=4,
            draft_model=CFG if draft else None, spec_gamma=3,
            spec_iters_per_sync=2),
            draft_params=(init_params(jax.random.PRNGKey(7), CFG)
                          if draft else None),
            token_bytes=token_bytes, eos_token_id=0)
        req = {"token_ids": list(PROMPT), "model": "m",
               "sampling": {"temperature": 0.0,
                            "guided": {"regex": "[a-f]{10}"}},
               "stop": {"max_tokens": 12, "stop_token_ids": [0]}}
        toks = []
        async for o in eng.generate(req, Context()):
            toks += o.get("token_ids", [])
        stats = eng._spec_stats
        await eng.close()
        return toks, stats

    base, _ = await run(draft=False)
    spec, stats = await run(draft=True)
    assert spec == base
    assert stats.num_draft_tokens > 0          # spec actually engaged
    body = bytes(t for t in spec if t != 0)
    assert len(body) == 10 and all(97 <= c <= 102 for c in body), body


async def test_spec_guided_mixed_batch_with_plain_lane():
    """A guided lane and a plain sampled lane share one spec burst."""
    token_bytes = [bytes([i]) if i < 256 else None
                   for i in range(CFG.vocab_size)]
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=96, max_batch_size=2,
        default_max_tokens=10, decode_steps_per_sync=4,
        draft_model=CFG, spec_gamma=2, spec_iters_per_sync=2),
        draft_params=init_params(jax.random.PRNGKey(3), CFG),
        token_bytes=token_bytes, eos_token_id=0)

    async def guided():
        req = {"token_ids": [1, 2, 3], "model": "m",
               "sampling": {"temperature": 0.7, "seed": 5,
                            "guided": {"choice": ["abcd", "wxyz"]}},
               "stop": {"max_tokens": 8, "stop_token_ids": [0]}}
        return [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", [])]

    async def plain():
        req = {"token_ids": [9, 8, 7], "model": "m",
               "sampling": {"temperature": 0.8, "seed": 11},
               "stop": {"max_tokens": 8}}
        return [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", [])]

    g, p = await asyncio.gather(guided(), plain())
    body = bytes(t for t in g if t != 0)
    assert body in (b"abcd", b"wxyz"), body
    assert len(p) == 8
    assert eng._spec_stats.num_draft_tokens > 0
    await eng.close()


async def _spec_tv_distance(min_p: float = 0.0,
                            penalties: bool = False) -> float:
    """Leviathan correctness, measured: over many lanes/seeds, the
    first spec-emitted token's empirical distribution must match
    target-only sampling from the same filtered (and, when enabled,
    penalty-adjusted / min_p-restricted) distribution. A biased
    acceptance rule shows up directly as TV distance."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.sampling import apply_penalties, filtered_probs
    from dynamo_tpu.engine.spec import spec_decode_multi_step
    from dynamo_tpu.models.llama import init_cache, prefill_step

    params = init_params(jax.random.PRNGKey(0), CFG)
    draft_params = init_params(jax.random.PRNGKey(99), CFG)
    B = 64
    reps = 4
    prompt = [3, 1, 4, 1]        # page-aligned (page_size 4): lanes can
    # share the READ-ONLY prompt page while writing their own proposals
    # into per-lane pages (shared write pages would race across lanes)
    n_pages = 2 + 2 * B
    kc, vc = init_cache(CFG, num_pages=n_pages)
    T = 8
    padded = np.zeros(T, dtype=np.int32)
    padded[:len(prompt)] = prompt
    prefill_table = np.zeros(CFG.max_pages_per_seq, dtype=np.int32)
    prefill_table[:2] = [1, 2]
    logits, kc, vc = prefill_step(
        params, kc, vc, jnp.asarray(padded), jnp.asarray(prefill_table),
        jnp.int32(0), jnp.int32(len(prompt)), CFG)
    dkc, dvc = init_cache(CFG, num_pages=n_pages)
    _, dkc, dvc = prefill_step(
        draft_params, dkc, dvc, jnp.asarray(padded),
        jnp.asarray(prefill_table), jnp.int32(0),
        jnp.int32(len(prompt)), CFG)
    lane_tables = np.zeros((B, CFG.max_pages_per_seq), dtype=np.int32)
    for i in range(B):
        lane_tables[i, 0] = 1                    # shared prompt page
        lane_tables[i, 1] = 3 + 2 * i            # private write pages
        lane_tables[i, 2] = 4 + 2 * i
    # the spec lanes are fed `cur` (position 4, KV unwritten); the first
    # emitted token is drawn at position 5 — the reference distribution
    # conditions on prompt + [cur]. top_k=8, temp 1.0: small support so
    # B*reps samples resolve it.
    del logits
    cur = 7
    temp, top_k = 1.0, 8
    V = CFG.vocab_size
    rkc, rvc = init_cache(CFG, num_pages=4)
    padded5 = np.zeros(T, dtype=np.int32)
    padded5[:5] = prompt + [cur]
    ref_table = np.zeros(CFG.max_pages_per_seq, dtype=np.int32)
    ref_table[:2] = [1, 2]
    ref_logits, _, _ = prefill_step(
        params, rkc, rvc, jnp.asarray(padded5), jnp.asarray(ref_table),
        jnp.int32(0), jnp.int32(5), CFG)
    # the emitted position's histograms: prompt tokens + the one output
    # token already emitted (cur) — what the engine's host counters
    # would hold at burst start
    p_cnt = np.zeros((1, V), dtype=np.int32)
    ids, cnts = np.unique(np.asarray(prompt), return_counts=True)
    p_cnt[0, ids] = cnts
    o_cnt = np.zeros((1, V), dtype=np.int32)
    o_cnt[0, cur] = 1
    rep, freq, pres = (1.4, 0.3, 0.2) if penalties else (1.0, 0.0, 0.0)
    ref_l = ref_logits[None].astype(jnp.float32)
    if penalties:
        ref_l = apply_penalties(
            ref_l, jnp.asarray(p_cnt), jnp.asarray(o_cnt),
            jnp.asarray([rep], jnp.float32),
            jnp.asarray([freq], jnp.float32),
            jnp.asarray([pres], jnp.float32))
    ref = np.asarray(filtered_probs(
        ref_l, jnp.asarray([temp]), jnp.asarray([1.0]),
        jnp.asarray([top_k]),
        jnp.asarray([min_p], jnp.float32) if min_p else None))[0]

    extra_kw = {}
    if min_p:
        extra_kw["min_p"] = jnp.full((B,), min_p, jnp.float32)
    if penalties:
        extra_kw.update(
            use_penalties=True,
            rep_pen=jnp.full((B,), rep, jnp.float32),
            freq_pen=jnp.full((B,), freq, jnp.float32),
            pres_pen=jnp.full((B,), pres, jnp.float32),
            prompt_counts=jnp.asarray(np.tile(p_cnt, (B, 1))),
            out_counts=jnp.asarray(np.tile(o_cnt, (B, 1))))

    counts = np.zeros(V)
    n = 0
    last_tok = cur
    for r in range(reps):
        # fresh caches each rep (donated by the spec call)
        kc2 = tuple(jnp.array(x) for x in kc)
        vc2 = tuple(jnp.array(x) for x in vc)
        dkc2 = tuple(jnp.array(x) for x in dkc)
        dvc2 = tuple(jnp.array(x) for x in dvc)
        packed, *_ = spec_decode_multi_step(
            params, draft_params, kc2, vc2, dkc2, dvc2,
            jnp.full((B,), last_tok, jnp.int32),
            jnp.full((B,), len(prompt), jnp.int32),
            jnp.asarray(lane_tables),
            jnp.ones((B,), bool),
            jnp.asarray(np.arange(B) + r * B, dtype=np.uint32),
            jnp.zeros((B,), jnp.uint32),
            jnp.full((B,), temp, jnp.float32),
            jnp.ones((B,), jnp.float32),
            jnp.full((B,), top_k, jnp.int32),
            CFG, CFG, 2, 1, **extra_kw)
        first = np.asarray(packed)[0, 0, 0, :].astype(np.int64)
        for t in first:
            counts[t] += 1
            n += 1
    emp = counts / n
    tv = 0.5 * np.abs(emp - ref).sum()
    if tv >= 0.25:
        raise AssertionError((tv, np.nonzero(counts)[0], ref.max()))
    return tv


async def test_spec_sampled_distribution_matches_target_only():
    # 256 samples over <=8 support: TV ~ O(sqrt(k/n)) ~ 0.12 expected
    await _spec_tv_distance()


async def test_spec_min_p_distribution_matches_target_only():
    # min_p shrinks the support; the spec-emitted distribution must
    # match target-only min_p sampling (r4: these lanes fell back)
    await _spec_tv_distance(min_p=0.15)


async def test_spec_penalty_distribution_matches_target_only():
    # penalties shift the logits identically on both sides; the
    # tentative-counts chain must not bias the first emitted token
    await _spec_tv_distance(penalties=True)


async def test_spec_topk_logprobs_match_no_spec():
    """Top-k logprob lanes RIDE the spec burst now (r3 excluded them):
    under greedy the packed top-k rows must match the no-spec engine's
    alternatives token for token, and speculation must actually engage."""
    params = init_params(jax.random.PRNGKey(0), CFG)

    async def run(draft):
        eng = TpuEngine(TpuEngineConfig(
            model=CFG, num_pages=96, max_batch_size=2,
            default_max_tokens=12, decode_steps_per_sync=4,
            draft_model=CFG if draft else None, spec_gamma=3,
            spec_iters_per_sync=2),
            params=params, draft_params=params if draft else None)
        req = {"token_ids": list(PROMPT), "model": "m",
               "sampling": {"temperature": 0.0, "top_logprobs": 3},
               "stop": {"max_tokens": 12}}
        toks, lps, topks = [], [], []
        async for o in eng.generate(req, Context()):
            toks += o.get("token_ids", [])
            lps += o.get("log_probs", []) or []
            topks += o.get("top_logprobs", []) or []
        stats = eng._spec_stats
        await eng.close()
        return toks, lps, topks, stats

    base_toks, base_lps, base_topks, _ = await run(draft=False)
    spec_toks, spec_lps, spec_topks, stats = await run(draft=True)
    assert spec_toks == base_toks
    assert stats is not None and stats.num_accepted_tokens > 0, \
        "top-k lanes must keep speculation, not fall back"
    assert len(spec_topks) == len(base_topks) == 12
    for st, bt in zip(spec_topks, base_topks):
        # the spec and no-spec bursts are separately compiled graphs:
        # bf16 near-ties can legitimately swap adjacent ALTERNATIVES'
        # order, so compare the candidate SET and align values by id
        assert {e[0] for e in st} == {e[0] for e in bt}, (st, bt)
        bvals = {e[0]: e[1] for e in bt}
        np.testing.assert_allclose([e[1] for e in st],
                                   [bvals[e[0]] for e in st], atol=2e-2)
        # top-1 is the chosen token under greedy — order matters THERE
    for t, st in zip(spec_toks, spec_topks):
        assert st[0][0] == t
