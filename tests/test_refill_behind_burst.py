"""Admission behind the block burst in flight (engine.py
`_land_block_burst`): while a block-diffusion engine waits for a burst, a
caller who arrives is admitted, and the newcomers' whole-block prefill
rounds go out as one batch: behind the burst once every lane is taken, else
when it lands, ahead of its emission. Such a caller must be served what an
idle engine serves it, to the last log-probability; a lane that ends inside
the burst is not handed on before the burst lands; a round launched behind
the burst writes none of its lanes' pages; a caller that finds no lane or no
pages waits for the landing as it always did; a dense engine never comes
this way."""

import asyncio
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.loader import config_from_hf, load_llama_params
from dynamo_tpu.runtime.context import Context
from tests import sdar_toy
from tests.sdar_toy import BLOCK

set_attention_impl("xla")

WIDTH = 4                                   # max_batch_size
PAGE = 8
SHARED = [int(t) for t in np.random.RandomState(5).randint(0, 290, 24)]


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sdar-toy"))
    sdar_toy.write_checkpoint(path)
    cfg = config_from_hf(path, dtype=jnp.float32, attn_block=BLOCK,
                         page_size=PAGE, max_pages_per_seq=16)
    return {"cfg": cfg, "params": load_llama_params(path, cfg)}


def make_engine(toy, width=WIDTH, num_pages=96):
    return TpuEngine(TpuEngineConfig(
        model=toy["cfg"], num_pages=num_pages, max_batch_size=width,
        prefill_chunk=32, decode_steps_per_sync=8, dllm_denoising_steps=4),
        params=toy["params"])


def pacer(max_tokens=48, prompt_len=13):
    rs = np.random.RandomState(2)
    return sdar_toy.request(
        [int(t) for t in rs.randint(0, 290, prompt_len)], max_tokens)


def arrival(i, max_tokens=None, **stop):
    """Three whole pages every arrival shares, then a tail of its own that
    leaves 1 + i % 4 ids of a block given; odd ones draw their tokens."""
    rs = np.random.RandomState(100 + i)
    tail = [int(t) for t in rs.randint(0, 290, 5 + i)]
    draws = dict(temperature=0.9, seed=11 + i, top_p=0.9) if i % 2 else {}
    req = sdar_toy.request(SHARED + tail, max_tokens or 9 + i, **draws)
    req["stop"].update(stop)
    return req


_ALONE: dict = {}


async def alone(toy, req):
    """What an idle engine serves the request."""
    key = repr(req)
    if key not in _ALONE:
        eng = make_engine(toy)
        try:
            _ALONE[key] = await sdar_toy.collect(eng, req)
            assert eng.pool.active_pages == 0
        finally:
            await eng.close()
    return _ALONE[key]


def same(got, want):
    assert got[0] == want[0] and got[3:] == want[3:], (got, want)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)


class Flight:
    """Holds the first burst of `eng` in flight (its host sync waits at a
    gate) until `release()`, and keeps what the engine did in order, each
    event at the start of its call: ("burst", pages its lanes own),
    ("round", pages it writes), ("landed",), ("emit",) a lane. `launched`
    is set when the first burst has been dispatched; `refused` counts the
    admissions that left someone waiting while a burst was in flight."""

    def __init__(self, eng):
        self.eng, self.events, self.refused = eng, [], 0
        self.launched = asyncio.Event()
        self._gate = threading.Event()
        loop = asyncio.get_running_loop()
        dispatch, sync, admit, emit = (eng._mesh_dispatch, eng._host_sync,
                                       eng._admit, eng._emit_lane)

        def spy_dispatch(trk, fn, *args, **kw):
            if trk.entry == "decode_burst":
                self.events.append(("burst", {
                    p for s in eng._running if s.prefilled
                    for p in s.pages}))
            elif trk.entry == "prefill":
                tables, cached, ends = (np.asarray(a) for a in args[4:7])
                self.events.append(("round", {
                    int(p) for row, c, e in zip(tables, cached, ends)
                    for p in row[c // PAGE:-(-e // PAGE)]}))
            out = dispatch(trk, fn, *args, **kw)
            if trk.entry == "decode_burst":
                loop.call_soon_threadsafe(self.launched.set)
            return out

        def gated_sync(packed):
            assert self._gate.wait(timeout=60)
            out = sync(packed)
            self.events.append(("landed",))
            return out

        def spy_emit(*args, **kw):
            self.events.append(("emit",))
            return emit(*args, **kw)

        def spy_admit():
            admit()
            kinds = self.kinds()
            self.refused += bool(eng._waiting) and (
                kinds.count("burst") > kinds.count("landed"))

        eng._mesh_dispatch, eng._host_sync = spy_dispatch, gated_sync
        eng._admit, eng._emit_lane = spy_admit, spy_emit

    def release(self):
        self._gate.set()

    def kinds(self):
        return [e[0] for e in self.events]

    async def until(self, cond, what):
        for _ in range(1000):
            if cond():
                return
            await asyncio.sleep(0.01)
        raise AssertionError(
            f"{what}: running={len(self.eng._running)} "
            f"waiting={len(self.eng._waiting)} "
            f"active_pages={self.eng.pool.active_pages} "
            f"events={self.kinds()}")

    async def at_rest(self):
        eng = self.eng
        await self.until(
            lambda: not eng._running and not eng._waiting
            and eng.pool.active_pages == 0, "not at rest")


async def behind_the_first_burst(eng, first, later, refills):
    """Serve `first` from an idle engine and `later` while its first burst
    is in flight: the burst lands once `refills` of them were admitted
    behind it (and prefilled, where that took the last lane) and the rest
    were turned away. Every collect() result, in that order."""
    flight = Flight(eng)
    tasks = [asyncio.create_task(sdar_toy.collect(eng, r)) for r in first]
    await flight.launched.wait()
    tasks += [asyncio.create_task(sdar_toy.collect(eng, r)) for r in later]
    full = len(first) + refills == eng.config.max_batch_size
    await flight.until(
        lambda: len(eng._running) == len(first) + refills
        and (refills == len(later) or flight.refused)
        and eng.perf["refills_behind_burst"] == refills * full,
        f"{refills} admitted behind the burst")
    assert "landed" not in flight.kinds()
    flight.release()
    got = [await t for t in tasks]
    await flight.at_rest()
    assert eng.perf["refills_behind_burst"] == refills
    return flight, got


# -- (a) served behind a burst what an idle engine serves --------------------


@pytest.mark.parametrize("hit", [False, True], ids=["cold", "prefix_hit"])
@pytest.mark.parametrize("n", [1, 2, WIDTH - 1, WIDTH])
async def test_arrivals_behind_a_burst_are_served_what_an_idle_engine_serves(
        toy, n, hit):
    later = [arrival(i) for i in range(n)]
    eng = make_engine(toy)
    cached = []
    alloc = eng._alloc_admission
    eng._alloc_admission = lambda h, t: cached.append(alloc(h, t)) \
        or cached[-1]
    try:
        if hit:
            await sdar_toy.collect(eng, sdar_toy.request(SHARED + [7], 3))
        # the pacer holds one lane: the others are refilled behind its
        # burst (in flight when they take every lane, else at its
        # landing), and with n == WIDTH the last waits for a lane to land
        flight, got = await behind_the_first_burst(
            eng, [pacer()], later, refills=min(n, WIDTH - 1))
    finally:
        await eng.close()
    lens = [c[1] for c in cached[-n:]]
    assert (lens[:WIDTH - 1] == [24] * min(n, WIDTH - 1)) if hit \
        else lens[0] == 0
    for res, req in zip(got, [pacer(), *later]):
        same(res, await alone(toy, req))


# -- (b) the round is on its way before the burst's tokens are emitted (before
# its sync returns, where every lane is taken), and (c) writes no page one of
# the burst's lanes owns ------------------------------------------------------


@pytest.mark.parametrize("width,order", [
    (2, ["round", "burst", "round", "landed", "emit"]),
    (WIDTH, ["round", "burst", "landed", "round", "emit"])],
    ids=["every_lane_taken", "a_lane_to_spare"])
async def test_the_round_is_dispatched_ahead_of_the_emission(
        toy, width, order):
    eng = make_engine(toy, width=width)
    try:
        flight, _ = await behind_the_first_burst(
            eng, [pacer()], [arrival(0)], refills=1)
    finally:
        await eng.close()
    # the pacer's own round and first burst, then the arrival's round:
    # behind the burst in flight where nobody else could join it, else
    # the first thing after the landing
    assert flight.kinds()[:5] == order
    owned = flight.events[1][1]
    written = flight.events[order.index("round", 1)][1]
    assert written and owned and not written & owned


@pytest.mark.parametrize("armed", [False, True], ids=["plain", "profiled"])
async def test_an_idle_engine_counts_no_refill_behind_a_burst(
        toy, armed, monkeypatch):
    """Requests that find the engine idle take the loop's own admit; the
    step recorder, armed, sees a burst as it did: one synced dispatch,
    its tokens counted with its forwards."""
    if armed:
        monkeypatch.setenv("DYN_STEP_PROFILE", "1")
    eng = make_engine(toy)
    try:
        got = await asyncio.gather(*(sdar_toy.collect(eng, arrival(i))
                                     for i in range(2)))
        assert eng.perf["refills_behind_burst"] == 0
        if armed:
            bursts = [r for r in eng.step_recorder.snapshot()
                      if r["entry"] == "decode_burst"]
            assert len(bursts) >= 2 and all(r["synced"] for r in bursts)
            m = eng.metrics
            assert m.block_forwards.get(kind="commit") * BLOCK \
                == m.goodput_tokens.get(entry="decode_burst")
    finally:
        await eng.close()
    for res, i in zip(got, range(2)):
        same(res, await alone(toy, arrival(i)))


# -- (c) a lane that ends inside the burst in flight is not handed on --------


async def test_a_lane_that_ends_in_flight_is_not_reused_before_the_landing(
        toy):
    """Both lanes of a two-lane engine end inside their first burst, one by
    max_tokens and one on a stop token; the caller who arrives while it is
    in flight gets a lane only once it has landed."""
    by_length = arrival(0, max_tokens=5)
    stop_at = (await alone(toy, arrival(2, max_tokens=16)))[0][2]
    by_stop = arrival(2, max_tokens=16, stop_token_ids=[stop_at])
    eng = make_engine(toy, width=2)
    try:
        flight, got = await behind_the_first_burst(
            eng, [by_length, by_stop], [arrival(1)], refills=0)
    finally:
        await eng.close()
    assert [g[3] for g in got] == ["length", "stop", "length"]
    # both lanes emit and end before the newcomer's round goes out
    assert flight.kinds()[:6] == ["round", "burst", "landed", "emit",
                                  "emit", "round"]
    for res, req in zip(got, [by_length, by_stop, arrival(1)]):
        same(res, await alone(toy, req))


# -- (d) cancelled after it was admitted behind a burst ----------------------


async def test_a_caller_cancelled_behind_the_burst_leaves_nothing_behind(
        toy):
    eng = make_engine(toy)
    try:
        flight = Flight(eng)
        first = asyncio.create_task(sdar_toy.collect(eng, pacer(24)))
        await flight.launched.wait()
        ctx, frames = Context(), []

        async def doomed():
            async for out in eng.generate(arrival(0), ctx):
                frames.append(out)

        task = asyncio.create_task(doomed())
        await flight.until(lambda: len(eng._running) == 2,
                           "not admitted behind the burst")
        assert eng._running[1].pages and "landed" not in flight.kinds()
        ctx.cancel()
        flight.release()
        await task
        same(await first, await alone(toy, pacer(24)))
        await flight.at_rest()
    finally:
        await eng.close()
    assert [f.get("finish_reason") for f in frames] == ["cancelled"]
    assert not any(f.get("token_ids") for f in frames)


# -- (e) nothing to admit it with: the old order ------------------------------


@pytest.mark.parametrize("lacks", ["lane", "pages"])
async def test_an_arrival_that_cannot_be_admitted_waits_for_the_landing(
        toy, lacks):
    if lacks == "lane":
        first, eng = [pacer(24)], make_engine(toy, width=1)
    else:
        # 12 pages: the pacer's 60 ids and its first burst hold 9 of
        # them, and 3 more would pass the pool's watermark
        first, eng = [pacer(16, prompt_len=60)], make_engine(
            toy, num_pages=13)
    try:
        flight, got = await behind_the_first_burst(
            eng, first, [arrival(3)], refills=0)
    finally:
        await eng.close()
    kinds = flight.kinds()
    # the arrival's round follows the emission that ended the pacer
    second = kinds.index("round", kinds.index("burst"))
    assert kinds[second - 2:second] == ["landed", "emit"]
    assert kinds[:second].count("burst") == kinds[:second].count("landed")
    for res, req in zip(got, [*first, arrival(3)]):
        same(res, await alone(toy, req))


# -- (f) a dense engine never takes the new arm -------------------------------


async def test_a_dense_engine_never_waits_in_the_block_path():
    eng = TpuEngine(TpuEngineConfig(
        model=LlamaConfig.tiny(), num_pages=128, max_batch_size=WIDTH,
        prefill_chunk=32, min_prefill_bucket=8, decode_steps_per_sync=4))
    entered = []

    async def refuse(packed):
        entered.append(packed)
        raise AssertionError("a dense engine in the block path")

    eng._land_block_burst = refuse
    try:
        started = asyncio.Event()

        async def long_one():
            async for out in eng.generate(
                    sdar_toy.request(range(1, 10), 40), Context()):
                started.set()

        first = asyncio.create_task(long_one())
        await started.wait()                    # a burst is in flight
        got = await asyncio.gather(*(sdar_toy.collect(
            eng, sdar_toy.request(range(3 + i, 12 + i), 9))
            for i in range(WIDTH)))
        await first
        assert [len(g[0]) for g in got] == [9] * WIDTH
        assert not entered and eng.perf["refills_behind_burst"] == 0
    finally:
        await eng.close()
