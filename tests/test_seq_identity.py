"""A sequence is itself (engine.py `_Seq`, `@dataclass(eq=False)`): the
scheduler keeps its sequences in lists and asks `s in self._running`,
`s not in runnable`, `.remove(s)` once a lane a burst, and a list scan
calls `__eq__` on every element ahead of the hit. With the dataclass's
generated `__eq__` that was lanes^2 / 2 comparisons of thirty fields a
burst (its emission, `_prep_decode_lanes`, `can_spec`); with identity it is
a scan of pointers, and no field of a sequence is ever compared.

(b) shows it from the outside: every sequence's first field (`req`, where a
field-by-field comparison of two distinct sequences starts and, the
requests differing, ends) is an object whose `__eq__` raises, and a run
that ends lanes by length and by a stop id inside a burst, cancels one and
preempts under page pressure serves what an idle engine serves. The three
engines are `tests/test_refill_behind_burst.py`'s toys."""

import asyncio
import dataclasses

import numpy as np

from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.engine import _Seq
from dynamo_tpu.protocols import PreprocessedRequest
from dynamo_tpu.runtime.context import Context
from tests import sdar_toy
from tests.test_refill_behind_burst import (  # noqa: F401 (`toy`: fixture)
    ALL, alone, make_engine, same, toy)

WIDTH = 16
STOPPED, CANCELLED = 10, 13              # lanes of (b)


def hand_built(req):
    return _Seq(req=req, ctx=Context("one"), queue=None, token_seq=None,
                prompt=[10, 11])


def test_two_sequences_of_the_same_fields_are_two_sequences():
    req = PreprocessedRequest.from_dict({
        "token_ids": [10, 11], "model": "m", "stop": {"max_tokens": 1}})
    a, b = hand_built(req), hand_built(req)
    b.ctx = a.ctx
    assert a == a and b == b and a != b and not a == b
    assert len({a, b}) == 2 and hash(a) != hash(b)
    assert b not in [a] and [a, b].index(b) == 1
    lst = [a, b]
    lst.remove(b)
    assert lst == [a] and lst[0] is a and {b: 1}[b] == 1


class Tripwire(PreprocessedRequest):
    """A request no scan may look at: a list scan over sequences that
    compares fields reaches `req` first."""
    compared: list = []

    def __eq__(self, other):
        Tripwire.compared.append((self.token_ids[:2], type(other).__name__))
        raise AssertionError("a sequence's fields were compared")

    __hash__ = None


def tripwire_every(monkeypatch, module, name):
    """Every record `module.<name>(...)` builds from now on carries a
    Tripwire for its request."""
    build = getattr(module, name)

    def tripwired(**fields):
        record = build(**fields)
        record.req.__class__ = Tripwire
        return record

    monkeypatch.setattr(module, name, tripwired)
    Tripwire.compared.clear()


def request(toy, i, max_tokens, **stop):
    rs = np.random.RandomState(300 + i)
    req = sdar_toy.request(
        [int(t) for t in rs.randint(0, toy.vocab, 11 + i % 5)], max_tokens)
    req["stop"].update(stop)
    return req


@ALL
async def test_no_scan_of_the_per_burst_path_compares_a_sequences_fields(
        toy, monkeypatch):
    """Sixteen lanes over several bursts: lanes end by length in different
    bursts, one on a stop id inside a burst, one is cancelled, and the pool
    runs dry while they grow, so `_prep_decode_lanes` preempts the youngest.
    Reached on the way: `_emit_burst` / the block emission, `can_spec`,
    `_prep_decode_lanes`, `_finish`, `_pick_victim`, `_preempt`."""
    tripwire_every(monkeypatch, engine_mod, "_Seq")
    lengths = [5, 9, 12, 17, 20, 23, 26, 30, 33, 36, 40, 40, 40, 40, 40, 40]
    reqs = [request(toy, i, n) for i, n in enumerate(lengths)]
    # lane 10 stops on an id it would first serve inside its second burst
    stream = (await alone(toy, reqs[STOPPED]))[0]
    stops_at = next(k for k in range(9, 16) if stream[k] not in stream[:k])
    reqs[STOPPED] = request(toy, STOPPED, 40,
                            stop_token_ids=[stream[stops_at]])
    # the 16 prompts take 32 pages and every lane is admitted at once; a
    # pool without bounds peaks at 72 pages, this one runs dry at 51
    eng = make_engine(toy, width=WIDTH, num_pages=52, watermark=1.0)
    preempted, preempt = [], eng._preempt
    eng._preempt = lambda s: preempted.append(s.arrival) or preempt(s)
    ctx, part = Context(), []

    async def cancelled():
        async for out in eng.generate(reqs[CANCELLED], ctx):
            part.extend(out.get("token_ids") or [])
            if len(part) >= 10:
                ctx.cancel()

    try:
        got = await asyncio.wait_for(asyncio.gather(*(
            cancelled() if i == CANCELLED else sdar_toy.collect(eng, r)
            for i, r in enumerate(reqs))), timeout=300)
        # a speculative successor may still hold the last lanes' pages
        for _ in range(500):
            if eng._inflight is None and not eng.pool.active_pages:
                break
            await asyncio.sleep(0.01)
        assert eng.pool.active_pages == 0 and not eng._running \
            and not eng._waiting
    finally:
        await eng.close()
    assert Tripwire.compared == []
    assert preempted, "the pool never ran dry: nobody was preempted"
    whole = (await alone(toy, reqs[CANCELLED]))[0]
    assert 10 <= len(part) < len(whole) and part == whole[:len(part)]
    # the dense toy is bf16: a row of a 16-wide batch rounds its
    # log-probabilities other than the same row alone (8.7e-4 read)
    loose = dataclasses.replace(toy, atol=5e-3) if toy.kind == "dense" \
        else toy
    for i, res in enumerate(got):
        if i == CANCELLED:
            continue
        toks, lps, frames, finish, error = await alone(
            toy, request(toy, i, lengths[i]))
        if i == STOPPED:
            toks, lps, finish = (toks[:stops_at + 1], lps[:stops_at + 1],
                                 "stop")
        same(loose, res, (toks, lps, frames, finish, error))
        assert len(res[0]) == (stops_at + 1 if i == STOPPED else lengths[i])


async def test_the_mock_engine_compares_no_field_either(monkeypatch):
    """The mocker's scheduler is the engine's twin (`r not in
    self._running` a lane an iteration, `.remove` at a finish and a
    preemption): sixteen decodes on a cache that cannot hold them."""
    from dynamo_tpu.mocker import engine as mock_mod
    from dynamo_tpu.mocker import MockEngine, MockEngineConfig

    tripwire_every(monkeypatch, mock_mod, "_MockRequest")
    eng = MockEngine(MockEngineConfig(
        speedup=500.0, block_size=2, total_kv_blocks=64, watermark=1.0,
        max_batch_size=WIDTH))
    preempted, preempt = [], eng._preempt
    eng._preempt = lambda r: preempted.append(r.arrival) or preempt(r)

    async def one(i):
        req = PreprocessedRequest(token_ids=[i, i + 1], model="m")
        req.stop.max_tokens = 6 + i
        return [t async for d in eng.generate(req.to_dict(), Context())
                for t in d["token_ids"]]

    try:
        got = await asyncio.wait_for(
            asyncio.gather(*(one(i) for i in range(WIDTH))), timeout=60)
    finally:
        await eng.close()
    assert [len(g) for g in got] == [6 + i for i in range(WIDTH)]
    assert Tripwire.compared == [] and preempted
    assert eng.kv.active_blocks == 0

