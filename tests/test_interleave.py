"""Token-budgeted chunked-prefill interleaving (engine._prefill_budgeted):
decode lanes must keep emitting BETWEEN a long prompt's chunk rounds, the
interleaving must not perturb any lane's tokens, and budget=0 must be the
legacy phase-alternating scheduler exactly."""

import asyncio
import time

import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime.context import Context

set_attention_impl("xla")

pytestmark = pytest.mark.tier0


def make_engine(**kw):
    defaults = dict(
        model=LlamaConfig.tiny(),
        num_pages=64, max_batch_size=4, prefill_chunk=32,
        min_prefill_bucket=8, default_max_tokens=8,
        decode_steps_per_sync=2)
    defaults.update(kw)
    return TpuEngine(TpuEngineConfig(**defaults))


def req(tokens, max_tokens=8, **sampling):
    return {"token_ids": list(tokens), "model": "m",
            "sampling": {"temperature": 0.0, **sampling},
            "stop": {"max_tokens": max_tokens}}


async def run(engine, request):
    return [o async for o in engine.generate(request, Context())]


async def _consume(engine, request, label, events):
    """Drain one request, appending (label, monotonic, token_count) per
    emission frame to the shared `events` log."""
    toks = []
    async for o in engine.generate(request, Context()):
        ids = o.get("token_ids", ())
        if ids:
            events.append((label, time.monotonic(), len(ids)))
            toks.extend(ids)
    return toks


async def _interleave_workload(eng, events):
    """TWO short decode lanes already streaming, then a long prompt:
    returns ([lane tokens...], long tokens, long submission time)."""
    lanes = [asyncio.create_task(_consume(
        eng, req(range(1 + i, 9 + i), max_tokens=40), f"short{i}",
        events)) for i in range(2)]
    while len({lab for lab, _, _ in events}) < 2:  # both lanes decoding
        await asyncio.sleep(0.01)
    t_submit = time.monotonic()
    long_toks = await _consume(
        eng, req(range(1, 41), max_tokens=5), "long", events)
    lane_toks = [await t for t in lanes]
    return lane_toks, long_toks, t_submit


async def test_decode_emits_between_prefill_chunks():
    # budget 8 on a 40-token prompt: >= 5 chunk rounds, each a separate
    # scheduler iteration with decode bursts between them
    eng = make_engine(prefill_chunk_budget=8)
    try:
        events = []
        lane_toks, long_toks, t_submit = \
            await _interleave_workload(eng, events)
        assert len(long_toks) == 5
        assert all(len(t) == 40 for t in lane_toks)
        t_first_long = next(t for lab, t, _ in events if lab == "long")
        between = [e for e in events
                   if e[0].startswith("short")
                   and t_submit < e[1] < t_first_long]
        assert between, (
            "no decode emission between long-prompt submission and its "
            f"first token — prefill stalled decode; events={events}")
        assert eng.perf["prefill_chunks"] >= 5
        assert eng.perf["decode_steps_during_prefill"] > 0
        assert eng.perf["mixed_steps"] > 0          # fused path exercised
        assert len(eng.itl_samples) > 0
        assert sum(eng.perf["itl_hist"]) == len(eng.itl_samples)
    finally:
        await eng.close()


async def test_interleaved_tokens_identical_to_legacy():
    # greedy outputs must be token-identical whether the engine
    # interleaved (budget>0, mixed steps) or phase-alternated (budget=0)
    results = {}
    for budget in (0, 8):
        eng = make_engine(prefill_chunk_budget=budget)
        try:
            events = []
            lane_toks, long_toks, _ = \
                await _interleave_workload(eng, events)
            results[budget] = (lane_toks, long_toks)
            if budget == 0:
                # budget=0 IS the legacy scheduler: no mixed steps, no
                # budgeted rounds, all-at-once prefill
                assert eng.perf["mixed_steps"] == 0
        finally:
            await eng.close()
    assert results[0] == results[8], results


async def test_non_fused_fallback_still_interleaves():
    # a penalties lane needs the constrained burst, which the mixed step
    # does not serve: the chunk round must run stand-alone and decode
    # must still progress between rounds
    eng = make_engine(prefill_chunk_budget=8)
    try:
        events = []
        short = asyncio.create_task(_consume(
            eng, req(range(1, 9), max_tokens=40, repetition_penalty=1.3),
            "short", events))
        while not events:
            await asyncio.sleep(0.01)
        long_toks = await _consume(
            eng, req(range(1, 41), max_tokens=5), "long", events)
        short_toks = await short
        assert len(long_toks) == 5 and len(short_toks) == 40
        assert eng.perf["mixed_steps"] == 0
        assert eng.perf["prefill_chunks"] >= 5
        assert eng.perf["decode_steps_during_prefill"] > 0
    finally:
        await eng.close()


async def test_budget_zero_matches_seed_behavior():
    # single-request sanity in both modes (the budgeted scheduler's
    # pure-prefill path, no decode lanes to fuse with)
    toks = {}
    for budget in (0, 8):
        eng = make_engine(prefill_chunk_budget=budget)
        try:
            outs = await run(eng, req(range(1, 41), max_tokens=6))
            toks[budget] = [t for o in outs
                            for t in o.get("token_ids", ())]
            assert outs[-1]["finish_reason"] == "length"
        finally:
            await eng.close()
    assert toks[0] == toks[8]


async def test_partial_prefill_excluded_from_decode():
    # while the cursor is mid-prompt the sequence must not enter decode
    # batches; after completion it decodes normally
    eng = make_engine(prefill_chunk_budget=4)
    try:
        outs = await run(eng, req(range(1, 33), max_tokens=4))
        ids = [t for o in outs for t in o.get("token_ids", ())]
        assert len(ids) == 4
        assert eng.perf["prefill_chunks"] >= 8
        # cursor bookkeeping: nothing left mid-prefill
        assert not eng._running and not eng._waiting
    finally:
        await eng.close()


async def _prefill_order(eng, prompts, max_tokens=3):
    """Serve `prompts` as one admission batch; returns (tokens per
    prompt, host-side order of launches: ("round", n sequences) and
    ("first", prompt lengths sampled))."""
    order = []
    round_once, first = eng._chunk_round_once, eng._first_token_dispatch

    def spy_round(params_, model_cfg, kc, vc, ready, *a, **kw):
        order.append(("round", len(ready)))
        return round_once(params_, model_cfg, kc, vc, ready, *a, **kw)

    def spy_first(pending, last_logits):
        order.append(("first", sorted(len(s.prompt) for s in pending)))
        return first(pending, last_logits)

    eng._chunk_round_once, eng._first_token_dispatch = spy_round, spy_first
    tasks = [asyncio.create_task(run(eng, req(p, max_tokens=max_tokens)))
             for p in prompts]
    outs = [await t for t in tasks]
    return [[t for o in out for t in o.get("token_ids", ())]
            for out in outs], order


@pytest.mark.parametrize("lengths, want", [
    # a one-chunk prompt goes first; the long one then takes its rounds
    # alone and neither waits for the other's
    ((8, 40), [("round", 1), ("first", [8]), ("round", 1), ("round", 1),
               ("round", 1), ("first", [40])]),
    # prompts of several chunks: in arrival order, one after the other
    ((40, 8, 24), [("round", 1), ("first", [8]), ("round", 1), ("round", 1),
                   ("round", 1), ("first", [40]), ("round", 1),
                   ("round", 1), ("first", [24])]),
    # prompts in their last chunk share a round, one group, one sync
    ((8, 12), [("round", 2), ("first", [8, 12])]),
    ((24, 40, 8), [("round", 1), ("first", [8]), ("round", 1), ("round", 1),
                   ("first", [24]), ("round", 1), ("round", 1),
                   ("round", 1), ("first", [40])]),
    ((40,), [("round", 1), ("round", 1), ("round", 1), ("first", [40])]),
])
async def test_first_token_follows_the_sequences_own_last_round(
        lengths, want):
    prompts = [list(range(1 + i, 1 + i + n)) for i, n in enumerate(lengths)]
    eng = make_engine(prefill_chunk=16)
    try:
        toks, order = await _prefill_order(eng, prompts)
        assert order == want
        assert not eng._running and not eng._waiting
    finally:
        await eng.close()
    # the grouping changes when a first token leaves, never which
    for p, got in zip(prompts, toks):
        alone = make_engine(prefill_chunk=16)
        try:
            outs = await run(alone, req(p, max_tokens=3))
        finally:
            await alone.close()
        assert got == [t for o in outs for t in o.get("token_ids", ())]


async def test_early_first_token_reaches_the_caller_before_the_batch_ends():
    # the short prompt's frame is on its stream before the long prompt's
    # first token is waited for
    eng = make_engine(prefill_chunk=16)
    events, order = [], []
    host_sync, emit = eng._host_sync, eng._emit_first_tokens

    def spy_sync(packed):
        order.append("sync")
        return host_sync(packed)

    def spy_emit(pending, *a, **kw):
        order.append(("emit", [len(s.prompt) for s in pending]))
        return emit(pending, *a, **kw)

    eng._host_sync, eng._emit_first_tokens = spy_sync, spy_emit
    try:
        long_ = asyncio.create_task(_consume(
            eng, req(range(1, 41), max_tokens=1), "long", events))
        short = asyncio.create_task(_consume(
            eng, req(range(2, 10), max_tokens=1), "short", events))
        await long_, await short
        assert [lab for lab, _, _ in events] == ["short", "long"]
        assert order[:4] == ["sync", ("emit", [8]), "sync", ("emit", [40])]
    finally:
        await eng.close()
