"""The refill chain: a prefill wave's first tokens sampled in one launch
a group, and the next decode burst launched behind the samplers before
any of them is synced (engine.py `_chain_burst`). A chained engine must
serve what an engine that syncs first serves, to the last log-probability;
lanes the chained burst overshoots must leave nothing behind; lanes the
plain burst cannot serve must keep the synced order."""

import asyncio

import jax
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime.context import Context

set_attention_impl("xla")

CFG = LlamaConfig.tiny()                    # vocab 256, page_size 4
WIDTH = 4                                   # max_batch_size


def make_engine(**kw):
    engine_kw = {k: kw.pop(k) for k in ("token_bytes", "eos_token_id")
                 if k in kw}
    defaults = dict(model=CFG, num_pages=128, max_batch_size=WIDTH,
                    prefill_chunk=32, min_prefill_bucket=8,
                    default_max_tokens=8, decode_steps_per_sync=4)
    defaults.update(kw)
    return TpuEngine(TpuEngineConfig(**defaults), **engine_kw)


def req(tokens, max_tokens=8, stop_ids=(), **sampling):
    return {"token_ids": list(tokens), "model": "m",
            "sampling": {"temperature": 0.0, "logprobs": True, **sampling},
            "stop": {"max_tokens": max_tokens,
                     "stop_token_ids": list(stop_ids)}}


async def serve(eng, request):
    """(token ids, their log-probabilities, finish reason) of one
    request."""
    toks, lps, finish = [], [], None
    async for o in eng.generate(request, Context()):
        toks += o.get("token_ids", ())
        lps += o.get("log_probs") or ()
        finish = o.get("finish_reason") or finish
    return toks, lps, finish


async def at_rest(eng):
    """Wait for the scheduler to park: the burst in flight landed and
    every page home."""
    for _ in range(100):
        if eng._inflight is None and eng.pool.active_pages == 0:
            return
        await asyncio.sleep(0.02)
    raise AssertionError(
        f"inflight={eng._inflight is not None} "
        f"active_pages={eng.pool.active_pages}")


# -- (a) chained == synced, arrivals behind a burst in flight ---------------


async def _pacer_then_group(eng, n, sampling):
    """A long request decoding alone (so a burst is in flight), then `n`
    requests at one instant: more than the free lanes when n == WIDTH,
    so the last waits for a lane to come free and refills it."""
    started = asyncio.Event()

    async def pacer():
        toks, lps = [], []
        async for o in eng.generate(
                req(range(1, 10), max_tokens=40, **sampling), Context()):
            toks += o.get("token_ids", ())
            lps += o.get("log_probs") or ()
            started.set()
        return toks, lps, None

    first = asyncio.create_task(pacer())
    await started.wait()
    group = [asyncio.create_task(serve(eng, req(
        range(3 + i, 10 + 2 * i), max_tokens=9 + i, **sampling)))
        for i in range(n)]
    return [await t for t in [first, *group]]


@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.9, "seed": 11, "top_p": 0.9}],
    ids=["greedy", "seeded"])
@pytest.mark.parametrize("n", [1, 3, WIDTH])
async def test_chained_engine_serves_what_the_synced_one_serves(
        n, sampling):
    outs = {}
    for pipeline in (False, True):
        eng = make_engine(pipeline_bursts=pipeline)
        try:
            outs[pipeline] = await _pacer_then_group(eng, n, sampling)
            await at_rest(eng)
            # the pacer found the engine idle: its burst went out behind
            # its own sampler whatever the group's timing did after
            assert (eng.perf["chained_refills"] >= 1) == pipeline
        finally:
            await eng.close()
    for (toks, lps, _), (want, want_lps, _) in zip(outs[True], outs[False]):
        assert toks == want
        assert lps == want_lps
    assert [len(t) for t, _, _ in outs[True]] == \
        [40, *(9 + i for i in range(n))]


# -- (b) a lane its first token ends is overshoot in the chained burst ------


@pytest.mark.parametrize("ends_by", ["max_tokens", "stop_id"])
async def test_lane_ended_by_its_first_token_leaves_nothing_behind(ends_by):
    eng = make_engine()
    try:
        short, long_ = range(1, 9), range(20, 31)
        first, _, _ = await serve(eng, req(short, max_tokens=1))
        want, want_lps, _ = await serve(eng, req(long_, max_tokens=10))
        await at_rest(eng)
        eng.clear_kv_blocks()
        before = eng.perf["chained_refills"]
        ender = (req(short, max_tokens=1) if ends_by == "max_tokens"
                 else req(short, max_tokens=12, stop_ids=first))
        (toks, _, finish), (toks2, lps2, finish2) = await asyncio.gather(
            serve(eng, ender), serve(eng, req(long_, max_tokens=10)))
        # one wave, one burst behind it, and the ended lane rode it
        assert eng.perf["chained_refills"] == before + 1
        assert toks == first
        assert finish == ("length" if ends_by == "max_tokens" else "stop")
        assert (toks2, lps2, finish2) == (want, want_lps, "length")
        await at_rest(eng)
    finally:
        await eng.close()


# -- (c) lanes the plain burst cannot serve keep the synced order -----------


@pytest.mark.parametrize("kind", ["guided", "penalty", "draft"])
async def test_constrained_and_draft_lanes_are_not_chained(kind):
    sampling, kw = {}, {}
    if kind == "guided":
        kw = dict(token_bytes=[bytes([i]) for i in range(256)],
                  eos_token_id=0)
        sampling = {"guided": {"choice": ["abc", "xyz"]}}
    elif kind == "penalty":
        sampling = {"repetition_penalty": 1.3}
    else:
        kw = dict(draft_model=CFG, spec_gamma=2, spec_iters_per_sync=2)
    eng = make_engine(**kw)
    try:
        toks, _, finish = await serve(
            eng, req(range(1, 9), max_tokens=6, stop_ids=[0], **sampling))
        assert toks and finish in ("length", "stop")
        assert eng.perf["chained_refills"] == 0
        if kind != "draft":
            # the engine itself chains: only the lane kept it synced
            await serve(eng, req(range(1, 9), max_tokens=6))
            assert eng.perf["chained_refills"] == 1
        await at_rest(eng)
    finally:
        await eng.close()


# -- (d) a group out of one round is one launch -----------------------------


@pytest.mark.parametrize("n", [1, 3])
async def test_first_tokens_of_one_round_are_one_launch(n, monkeypatch):
    eng = make_engine()
    launches, eager_stacks = [], []
    dispatch, stack = eng._mesh_dispatch, jax.numpy.stack

    def spy_dispatch(trk, fn, *args, **kw):
        if trk.entry == "sample_first":
            launches.append((args[0].shape, kw["rows"]))
        return dispatch(trk, fn, *args, **kw)

    def spy_stack(arrays, *a, **kw):
        if not any(isinstance(x, jax.core.Tracer) for x in arrays):
            eager_stacks.append(len(arrays))
        return stack(arrays, *a, **kw)

    eng._mesh_dispatch = spy_dispatch
    monkeypatch.setattr(jax.numpy, "stack", spy_stack)
    try:
        await asyncio.gather(*(
            serve(eng, req(range(1 + i, 9 + i), max_tokens=3))
            for i in range(n)))
        # the round's own (Bp, V) logits, the rows picked in the program
        (shape, rows), = launches
        assert shape == (eng._prefill_width(n), CFG.vocab_size)
        assert list(rows) == [*range(n), *[0] * (WIDTH - n)]
        assert not eager_stacks
    finally:
        await eng.close()


# -- (e) one decode program however a burst's tokens were made ---------------


async def test_mesh_engine_hands_every_burst_its_tokens_alike(
        cpu_mesh_devices):
    """A jitted entry is lowered anew for each placement of its inputs.
    The chained burst's tokens come from a sampler and the speculative
    burst's from a burst, replicated over the mesh; a burst built from
    the host has to arrive the same way, or its program is first
    compiled at the first drain nobody refills: mid-serving."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]).reshape(1, 2),
                axis_names=("dp", "tp"))
    eng = make_engine(mesh=mesh)
    seen = []
    dispatch = eng._mesh_dispatch

    def spy_dispatch(trk, fn, *args, **kw):
        if trk.entry == "decode_burst":
            seen.append((args[3].sharding, args[3]._committed))
        return dispatch(trk, fn, *args, **kw)

    eng._mesh_dispatch = spy_dispatch
    try:
        # the short one ends first: chained, speculative, then rebuilt
        # from the host for the lane that is left
        await asyncio.gather(serve(eng, req(range(1, 9), max_tokens=6)),
                             serve(eng, req(range(2, 12), max_tokens=30)))
        chained, piped = (eng.perf["chained_refills"],
                          eng.perf["pipelined_bursts"])
        assert chained == 1 and piped >= 1
        assert len(seen) - chained - piped >= 1     # built from the host
        assert len(set(seen)) == 1, set(seen)
    finally:
        await eng.close()
