"""Admission behind the burst in flight (engine.py `_land_burst`): while an
engine waits for a burst that has no successor, a caller who arrives is
admitted, and the newcomers' prefill rounds go out as one batch: behind the
burst once every lane is taken, else when it lands, ahead of its emission.
Such a caller must be served what an idle engine serves it, to the last
log-probability; a lane that ends inside the burst is not handed on before
the burst lands; a round launched behind the burst writes none of its lanes'
pages or state slots; a caller that finds no lane or no pages waits for the
landing as it always did; an engine whose prefill is not the by-sequence
rounds, and a wave the refill chain would refuse, keep the old order.

Three engines come this way and are the `toy` parameter: a block-diffusion
one (SDAR toy: every burst is synced, so every burst is such a wait), a
dense one and one with recurrent layers (Nemotron toy). A dense burst has
no successor when a lane left the batch or somebody waits: there a caller
of one token starts with the pacer and leaves with its first token."""

import asyncio
import dataclasses
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.models.loader import config_from_hf, load_llama_params
from dynamo_tpu.runtime.context import Context
from tests import nemotron_toy, sdar_toy
from tests.sdar_toy import BLOCK

set_attention_impl("xla")

WIDTH = 4                                   # max_batch_size
PAGE = 8
SHARED = [int(t) for t in np.random.RandomState(5).randint(0, 250, 24)]


@dataclasses.dataclass
class Toy:
    kind: str
    cfg: object
    params: object
    vocab: int                  # prompts draw ids below it
    engine_kw: dict
    # the one-token caller that leaves the dense pacer's first burst
    # without a successor; a block burst never has one
    ender: bool = True
    # log-probabilities: the scan's sums of a wider round differ more
    atol: float = 1e-5


_TOYS: dict = {}


def _build(kind, tmp_path_factory) -> Toy:
    if kind == "dense":
        return Toy(kind, LlamaConfig.tiny(page_size=PAGE), None, 250,
                   dict(prefill_chunk=32, min_prefill_bucket=8))
    path = str(tmp_path_factory.mktemp(f"{kind}-toy"))
    if kind == "block":
        sdar_toy.write_checkpoint(path)
        cfg = config_from_hf(path, dtype=jnp.float32, attn_block=BLOCK,
                             page_size=PAGE, max_pages_per_seq=16)
        kw = dict(prefill_chunk=32, dllm_denoising_steps=4)
    else:
        nemotron_toy.write_checkpoint(path)
        cfg = config_from_hf(path, dtype=jnp.float32, page_size=PAGE,
                             max_pages_per_seq=16)
        kw = dict(prefill_chunk=16)         # an arrival's prompt: 2 chunks
    return Toy(kind, cfg, load_llama_params(path, cfg), 290, kw,
               ender=kind != "block",
               atol=1e-5 if kind == "block" else 5e-5)


@pytest.fixture
def toy(request, tmp_path_factory):
    if request.param not in _TOYS:
        _TOYS[request.param] = _build(request.param, tmp_path_factory)
    return _TOYS[request.param]


def kinds(*names):
    return pytest.mark.parametrize("toy", names, indirect=True)


ALL = kinds("block", "dense", "recurrent")


def make_engine(toy, width=WIDTH, num_pages=96, engine_kw=None, **kw):
    return TpuEngine(TpuEngineConfig(
        model=toy.cfg, num_pages=num_pages, max_batch_size=width,
        decode_steps_per_sync=8, **{**toy.engine_kw, **kw}),
        params=toy.params, **(engine_kw or {}))


def pacer(toy, max_tokens=48, prompt_len=13):
    rs = np.random.RandomState(2)
    return sdar_toy.request(
        [int(t) for t in rs.randint(0, toy.vocab, prompt_len)], max_tokens)


def ender(toy):
    rs = np.random.RandomState(3)
    return sdar_toy.request([int(t) for t in rs.randint(0, toy.vocab, 9)], 1)


def arrival(toy, i, max_tokens=None, stop=None, **sampling):
    """Three whole pages every arrival shares, then a tail of its own that
    leaves 1 + i % 4 ids of a block given; odd ones draw their tokens."""
    rs = np.random.RandomState(100 + i)
    tail = [int(t) for t in rs.randint(0, toy.vocab, 5 + i)]
    draws = dict(temperature=0.9, seed=11 + i, top_p=0.9) if i % 2 else {}
    req = sdar_toy.request(SHARED + tail, max_tokens or 9 + i,
                           **{**draws, **sampling})
    req["stop"].update(stop or {})
    return req


_ALONE: dict = {}


async def alone(toy, req):
    """What an idle engine serves the request."""
    key = toy.kind + repr(req)
    if key not in _ALONE:
        eng = make_engine(toy)
        try:
            _ALONE[key] = await sdar_toy.collect(eng, req)
            assert eng.pool.active_pages == 0
        finally:
            await eng.close()
    return _ALONE[key]


def same(toy, got, want):
    assert got[0] == want[0] and got[3:] == want[3:], (got, want)
    np.testing.assert_allclose(got[1], want[1], atol=toy.atol)


class Flight:
    """Holds the first burst of `eng` (or its `hold_at`-th) in flight (its
    host sync waits at a gate; a first-token sampler's does not) until
    `release()`, and keeps
    what the engine did in order, each event at the start of its call:
    ("burst", pages its lanes own, their state slots), ("round", pages it
    writes, slots it writes, slots the decoding lanes hold: a lane that
    ended gave its slot back at once, its next tenant starts from zero
    behind the burst), ("landed",) a burst, ("emit",) a lane.
    `launched` is set when the held burst has been dispatched; `refused`
    counts the admissions that left someone waiting while a burst was in
    flight."""

    def __init__(self, eng, hold_at=1):
        self.eng, self.events, self.refused = eng, [], 0
        # the burst held at the gate, counted from 1 as dispatched
        self.hold_at = hold_at
        self.launched = asyncio.Event()
        self._gate = threading.Event()
        loop = asyncio.get_running_loop()
        dispatch, sync, admit, emit = (eng._mesh_dispatch, eng._host_sync,
                                       eng._admit, eng._emit_lane)

        def spy_dispatch(trk, fn, *args, **kw):
            if trk.entry == "decode_burst":
                self.events.append(("burst", {
                    p for s in eng._running for p in s.pages},
                    {s.slot for s in eng._running} - {0}))
            elif trk.entry == "prefill":
                tables, cached, ends = (np.asarray(a) for a in args[4:7])
                self.events.append(("round", {
                    int(p) for row, c, e in zip(tables, cached, ends)
                    for p in row[c // PAGE:-(-e // PAGE)]},
                    {int(x) for x in np.asarray(kw.get("slots", ()))}
                    - {0},
                    {s.slot for s in eng._running if s.prefilled} - {0}))
            out = dispatch(trk, fn, *args, **kw)
            if (trk.entry == "decode_burst"
                    and self.kinds().count("burst") == self.hold_at):
                loop.call_soon_threadsafe(self.launched.set)
            return out

        def gated_sync(packed):
            if packed.ndim < 3:                     # first tokens
                return sync(packed)
            if self.kinds().count("landed") + 1 >= self.hold_at:
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

    def kinds(self, since_burst=False):
        out = [e[0] for e in self.events]
        return out[out.index("burst"):] if since_burst else out

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
            and eng._inflight is None and eng.pool.active_pages == 0
            and (eng.slots is None or eng.slots.in_use == 0),
            "not at rest")


async def hold_the_first_burst(eng, toy, first, with_ender=None):
    """Start `first` on an idle engine and hold their first burst in
    flight, with no successor: (the recorder, the callers' tasks)."""
    flight = Flight(eng)
    tasks = [asyncio.create_task(sdar_toy.collect(eng, r)) for r in first]
    if toy.ender if with_ender is None else with_ender:
        left = asyncio.create_task(sdar_toy.collect(eng, ender(toy)))
        left.add_done_callback(lambda t: t.result())
    await flight.launched.wait()
    await flight.until(lambda: len(eng._running) == len(first),
                       "the one-token caller has not left")
    return flight, tasks


async def behind_the_first_burst(eng, toy, first, later, refills,
                                 with_ender=None):
    """Serve `first` from an idle engine and `later` while its first burst
    is in flight: the burst lands once `refills` of them were admitted
    behind it (and prefilled, where that took the last lane) and the rest
    were turned away. Every collect() result, in that order."""
    flight, tasks = await hold_the_first_burst(eng, toy, first, with_ender)
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


@pytest.mark.parametrize("toy,hit", [
    ("block", False), ("block", True), ("dense", False), ("dense", True),
    ("recurrent", False)], indirect=["toy"],
    ids=["block-cold", "block-prefix_hit", "dense-cold", "dense-prefix_hit",
         "recurrent-cold"])
@pytest.mark.parametrize("n", [1, 2, WIDTH - 1, WIDTH])
async def test_arrivals_behind_a_burst_are_served_what_an_idle_engine_serves(
        toy, n, hit):
    later = [arrival(toy, i) for i in range(n)]
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
            eng, toy, [pacer(toy)], later, refills=min(n, WIDTH - 1))
    finally:
        await eng.close()
    lens = [c[1] for c in cached[-n:]]
    assert (lens[:WIDTH - 1] == [24] * min(n, WIDTH - 1)) if hit \
        else lens[0] == 0
    for res, req in zip(got, [pacer(toy), *later]):
        same(toy, res, await alone(toy, req))


# -- (b) the round is on its way before the burst's tokens are emitted (before
# its sync returns, where every lane is taken), and (c) writes no page or slot
# one of the burst's lanes owns ------------------------------------------------

# from the first burst on. A dense wave also emits its first tokens (two
# callers' before the arrival came) and, every lane taken, its burst is
# chained behind its sampler ahead of the arrival's first token
ORDERS = {
    ("block", 2): ["burst", "round", "landed", "emit"],
    ("block", WIDTH): ["burst", "landed", "round", "emit"],
    ("dense", 2): ["burst", "emit", "emit", "round", "landed", "emit",
                   "burst", "emit"],
    # a lane to spare: the burst waits for the first token (whoever
    # arrives meanwhile is prefilled ahead of it)
    ("dense", WIDTH): ["burst", "emit", "emit", "landed", "round", "emit",
                       "emit", "burst"],
}


@ALL
@pytest.mark.parametrize("width", [2, WIDTH],
                         ids=["every_lane_taken", "a_lane_to_spare"])
async def test_the_round_is_dispatched_ahead_of_the_emission(toy, width):
    eng = make_engine(toy, width=width)
    try:
        flight, _ = await behind_the_first_burst(
            eng, toy, [pacer(toy)], [arrival(toy, 0)], refills=1)
    finally:
        await eng.close()
    # the arrival's rounds: behind the burst in flight where nobody else
    # could join them, else the first thing after the landing
    order = flight.kinds(since_burst=True)
    if toy.kind == "recurrent":             # a prompt of two chunks
        order.remove("round")
    want = ORDERS["block" if toy.kind == "block" else "dense", width]
    assert order[:len(want)] == want
    at = flight.kinds().index("burst")
    _, pages, slots = flight.events[at]
    rounds = [e for e in flight.events[at:] if e[0] == "round"]
    assert len(rounds) == 1 + (toy.kind == "recurrent")
    for _, written, reset, held in rounds:
        assert written and pages and not written & pages
        assert bool(reset) == bool(slots) == (toy.kind == "recurrent")
        assert held <= slots and not reset & held


@kinds("dense", "recurrent")
async def test_a_burst_with_a_successor_is_waited_for_awake_too(toy):
    """A pacer alone has its next burst launched ahead of the sync. The
    caller who arrives meanwhile is admitted at once, its rounds go out at
    the landing ahead of the emission (behind the successor), and its first
    token stays on the device until the successor has landed: no burst is
    speculated past a wave."""
    eng = make_engine(toy)
    try:
        flight, tasks = await hold_the_first_burst(
            eng, toy, [pacer(toy)], with_ender=False)
        await flight.until(lambda: eng.perf["pipelined_bursts"] == 1,
                           "no successor launched")
        tasks.append(asyncio.create_task(
            sdar_toy.collect(eng, arrival(toy, 0))))
        await flight.until(lambda: len(eng._running) == 2,
                           "not admitted behind the burst")
        assert "landed" not in flight.kinds()
        flight.release()
        got = [await t for t in tasks]
        await flight.at_rest()
        assert eng.perf["refills_behind_burst"] == 1
    finally:
        await eng.close()
    kinds_ = flight.kinds(since_burst=True)
    order = [k for k in kinds_ if k != "round"]
    assert kinds_[:8].count("round") == 1 + (toy.kind == "recurrent")
    # the pacer's first token, the successor; both bursts landed and
    # emitted; the arrival's first token; the wave's burst (lanes to
    # spare: not chained)
    assert order[:9] == ["burst", "emit", "burst", "landed", "emit",
                         "landed", "emit", "emit", "burst"]
    assert kinds_.index("round") == kinds_.index("landed") + 1
    for res, req in zip(got, [pacer(toy), arrival(toy, 0)]):
        same(toy, res, await alone(toy, req))


@kinds("dense", "recurrent")
@pytest.mark.parametrize("comes_back", [True, False])
async def test_a_successor_is_held_for_the_caller_of_a_lane_that_ended(
        toy, comes_back):
    """A lane that ended at the last emission has, in a closed loop, a
    caller on its way back: the burst built after it keeps its successor
    until most of its device time has passed. The caller who comes back
    meanwhile is prefilled behind THAT burst (no successor is launched);
    where nobody comes, the successor goes out ahead of the landing."""
    # `one` ends with burst 1 (so burst 2, speculated, lands alone) and
    # `two` with burst 2's last token: burst 3 is built from the host
    # right after a lane ended, a lane to spare
    one, two = arrival(toy, 0, max_tokens=5), arrival(toy, 2, max_tokens=17)
    eng = make_engine(toy)
    if comes_back:
        eng._SPEC_HOLD = 1e6                # the deadline never passes
    try:
        flight = Flight(eng, hold_at=3)
        tasks = [asyncio.create_task(sdar_toy.collect(eng, r))
                 for r in (pacer(toy), one, two)]
        await flight.launched.wait()
        assert eng.perf["pipelined_bursts"] == 1 and len(eng._running) == 1
        if comes_back:
            tasks.append(asyncio.create_task(
                sdar_toy.collect(eng, arrival(toy, 1))))
            await flight.until(lambda: len(eng._running) == 2,
                               "not admitted behind the burst")
            await asyncio.sleep(0.05)
            assert eng.perf["pipelined_bursts"] == 1
        else:
            await flight.until(lambda: eng.perf["pipelined_bursts"] == 2,
                               "the held successor was never launched")
        assert flight.kinds().count("landed") == 2
        flight.release()
        got = [await t for t in tasks]
        await flight.at_rest()
        assert eng.perf["refills_behind_burst"] == comes_back
    finally:
        await eng.close()
    if comes_back:
        # burst 3 landed, the round ahead of its emission, the wave's
        # burst chained: no burst between
        order = flight.kinds()
        at = [i for i, k in enumerate(order) if k == "landed"][2]
        assert order[at + 1] == "round"
        assert order[at:].index("burst") > order[at:].index("emit")
    for res, req in zip(got, [pacer(toy), one, two, arrival(toy, 1)]):
        same(toy, res, await alone(toy, req))


@kinds("block")
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
        got = await asyncio.gather(*(sdar_toy.collect(eng, arrival(toy, i))
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
        same(toy, res, await alone(toy, arrival(toy, i)))


# -- (c) a lane that ends inside the burst in flight is not handed on --------


@ALL
async def test_a_lane_that_ends_in_flight_is_not_reused_before_the_landing(
        toy):
    """Both lanes of a two-lane engine end inside their first burst, one by
    max_tokens and one on a stop token; the caller who arrives while it is
    in flight gets a lane only once it has landed."""
    by_length = arrival(toy, 0, max_tokens=5)
    stop_at = (await alone(toy, arrival(toy, 2, max_tokens=9)))[0][2]
    by_stop = arrival(toy, 2, max_tokens=9,
                      stop={"stop_token_ids": [stop_at]})
    eng = make_engine(toy, width=2)
    try:
        # neither has a burst's tokens to go: no successor as it is
        flight, got = await behind_the_first_burst(
            eng, toy, [by_length, by_stop], [arrival(toy, 1)], refills=0,
            with_ender=False)
    finally:
        await eng.close()
    assert [g[3] for g in got] == ["length", "stop", "length"]
    # both lanes emit and end before the newcomer's round goes out
    order = flight.kinds(since_burst=True)
    landed = order.index("landed")
    assert "round" not in order[:landed]
    assert order[landed:landed + 4] == ["landed", "emit", "emit", "round"]
    for res, req in zip(got, [by_length, by_stop, arrival(toy, 1)]):
        same(toy, res, await alone(toy, req))


# -- (d) cancelled after it was admitted behind a burst ----------------------


@ALL
async def test_a_caller_cancelled_behind_the_burst_leaves_nothing_behind(
        toy):
    eng = make_engine(toy)
    try:
        flight, (first,) = await hold_the_first_burst(
            eng, toy, [pacer(toy, 24)])
        ctx, frames = Context(), []

        async def doomed():
            async for out in eng.generate(arrival(toy, 0), ctx):
                frames.append(out)

        task = asyncio.create_task(doomed())
        await flight.until(lambda: len(eng._running) == 2,
                           "not admitted behind the burst")
        assert eng._running[1].pages and "landed" not in flight.kinds()
        ctx.cancel()
        flight.release()
        await task
        same(toy, await first, await alone(toy, pacer(toy, 24)))
        await flight.at_rest()
    finally:
        await eng.close()
    assert frames[-1].get("finish_reason") == "cancelled"
    if toy.kind == "block":
        assert len(frames) == 1 and not frames[0].get("token_ids")


# -- (e) nothing to admit it with: the old order ------------------------------


@ALL
@pytest.mark.parametrize("lacks", ["lane", "pages"])
async def test_an_arrival_that_cannot_be_admitted_waits_for_the_landing(
        toy, lacks):
    if lacks == "lane":
        # one lane; a dense pacer that ends inside its first burst has no
        # burst launched behind it
        first = [pacer(toy, 24 if toy.kind == "block" else 9)]
        eng, with_ender = make_engine(toy, width=1), False
    else:
        # the pacer's 60 ids and its first burst hold 9 pages (the
        # one-token caller's 3 wait in the burst's deferred list), and 4
        # more would pass the pool's watermark
        first, with_ender = [pacer(toy, 16, prompt_len=60)], None
        eng = make_engine(toy, num_pages=16 if toy.ender else 13)
    try:
        flight, got = await behind_the_first_burst(
            eng, toy, first, [arrival(toy, 3)], refills=0,
            with_ender=with_ender)
    finally:
        await eng.close()
    kinds = flight.kinds(since_burst=True)
    # the arrival's round follows the emission that ended the burst
    second = kinds.index("round")
    assert kinds[second - 2:second] == ["landed", "emit"]
    assert kinds[:second].count("burst") == kinds[:second].count("landed")
    for res, req in zip(got, [*first, arrival(toy, 3)]):
        same(toy, res, await alone(toy, req))


# -- (f) the old order: engines whose prefill is not the by-sequence rounds,
# and a wave the refill chain would refuse -------------------------------------


class _NoPeers:
    """A remote KVBM tier that holds nothing."""

    async def fetch(self, hashes, expect_shape=None):
        return []

    def status(self):
        return {}


TOKEN_BYTES = [bytes([i]) for i in range(256)]
OLD_ORDER = {
    "plain": ({}, {}, {}, 1),               # the control: the new order
    "draft": (dict(spec_gamma=2, spec_iters_per_sync=2), {}, {}, 0),
    "pp": ({}, {}, {}, 0),
    "budgeted": (dict(prefill_chunk_budget=32), {}, {}, 0),
    "ragged": ({}, {}, {}, 0),
    "remote_kvbm": ({}, {}, {}, 0),
    "guided": ({}, dict(token_bytes=TOKEN_BYTES, eos_token_id=0),
               {"guided": {"choice": ["abc", "xyz"]}}, 0),
    "penalised": ({}, {}, {"repetition_penalty": 1.3}, 0),
}


@kinds("dense")
@pytest.mark.parametrize("how", list(OLD_ORDER))
async def test_the_old_order_is_kept_where_the_refill_is_not_the_chains(
        toy, how, cpu_mesh_devices):
    """A pacer that ends inside its first burst (no successor) and a caller
    who arrives while it is in flight: admitted and prefilled behind it on
    a plain dense engine, after the landing and the emission on every
    other, with the counter at 0 and the tokens of an idle plain engine."""
    config_kw, engine_kw, sampling, counted = OLD_ORDER[how]
    config_kw = dict(config_kw)
    if how == "draft":
        config_kw["draft_model"] = toy.cfg
    elif how == "pp":
        from jax.sharding import Mesh

        config_kw.update(pp_microbatches=2, pp_mesh=Mesh(
            np.asarray(cpu_mesh_devices[:2]), axis_names=("pp",)))
    elif how == "ragged":
        set_attention_impl("ragged")
    try:
        eng = make_engine(toy, engine_kw=engine_kw, **config_kw)
        assert eng._refills_behind_burst == (how not in (
            "draft", "pp", "budgeted", "ragged"))
        if how == "draft":
            eng.spec_shrink = True          # plain bursts, in flight
        elif how == "remote_kvbm":
            from dynamo_tpu.kvbm import KvbmConfig, KvbmManager

            KvbmManager(eng, KvbmConfig(host_blocks=16)).remote = _NoPeers()
        late = arrival(toy, 0, **sampling)
        if how in ("pp", "ragged"):
            # every burst of theirs is synced where it is launched
            got = await asyncio.gather(
                sdar_toy.collect(eng, pacer(toy, 9)),
                sdar_toy.collect(eng, late))
            assert eng.perf["refills_behind_burst"] == 0
            assert eng.perf["pipelined_bursts"] == 0
        else:
            flight, tasks = await hold_the_first_burst(
                eng, toy, [pacer(toy, 9)], with_ender=False)
            tasks.append(asyncio.create_task(sdar_toy.collect(eng, late)))
            admitted = how in ("plain", "guided", "penalised")
            await flight.until(
                lambda: len(eng._running) == 1 + admitted
                and len(eng._waiting) == 1 - admitted,
                "the arrival is not in")
            await asyncio.sleep(0.05)
            order = flight.kinds(since_burst=True)
            assert "landed" not in order and "round" not in order
            flight.release()
            got = [await t for t in tasks]
            await flight.at_rest()
            assert eng.perf["refills_behind_burst"] == counted
            order = flight.kinds(since_burst=True)
            at = order.index("landed")
            assert order[at:at + 3] == (
                ["landed", "round", "emit"] if counted
                else ["landed", "emit", "round"])
    finally:
        set_attention_impl("xla")
        await eng.close()
    assert [len(g[0]) for g in got] == [9, 9] and got[0][3] == "length"
    for res, req in zip(got, [pacer(toy, 9), late][:2 - bool(sampling)]):
        want = await alone(toy, req)
        if how == "pp":                     # microbatches sum otherwise
            assert res[0] == want[0]
        else:
            same(toy, res, want)
