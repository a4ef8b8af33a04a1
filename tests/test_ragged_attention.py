"""Ragged paged attention (engine/ragged.py + the engine's ragged_step
entry): interpret-mode kernel parity against the XLA flat reference,
block_choice pins, fallback attribution, and chip-free e2e equivalence —
greedy streams must be identical ragged-on vs ragged-off, the ragged-off
serving path must not dispatch the ragged entry, and the flat-token
bucketing must strictly shrink the distinct compile-shape count on a
mixed workload."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import attention
from dynamo_tpu.engine.attention import (block_choice, ragged_enabled,
                                         set_attention_impl)
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.engine.ragged import (ragged_attention_xla,
                                      ragged_paged_attention,
                                      ragged_supported)
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime.context import Context

set_attention_impl("xla")

pytestmark = pytest.mark.tier0


@pytest.fixture(autouse=True)
def _restore_impl():
    yield
    set_attention_impl("xla")


# ---------------------------------------------------------------------------
# kernel parity (pallas interpret mode, chip-free)


def _ragged_case(rng, t_rows, h, kvh, d, n_pages, p, max_pages, qpos):
    """Build one flat-token case: random caches, per-row lane routing."""
    lanes_n = 4
    q = jnp.asarray(rng.standard_normal((t_rows, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((kvh, n_pages, p, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((kvh, n_pages, p, d)), jnp.float32)
    # distinct non-zero pages per lane so a table-indexing bug shows up
    tables = rng.permutation(np.arange(1, 1 + lanes_n * max_pages)) \
        .reshape(lanes_n, max_pages).astype(np.int32)
    token_lanes = jnp.asarray(rng.integers(0, lanes_n, t_rows), jnp.int32)
    token_qpos = jnp.asarray(qpos, jnp.int32)
    return q, k, v, token_qpos, token_lanes, jnp.asarray(tables)


def _assert_parity(args):
    got = ragged_paged_attention(*args, interpret=True)
    want = ragged_attention_xla(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    return np.asarray(got)


@pytest.mark.parametrize("h,kvh", [(4, 2), (4, 4), (8, 2)])
def test_kernel_parity_gqa_ragged_lengths(h, kvh):
    rng = np.random.default_rng(7 + h * 10 + kvh)
    t_rows, d, p, max_pages = 12, 128, 8, 6
    # ragged mix: positions straddle page boundaries (7->8), first page,
    # deep context
    qpos = [0, 3, 7, 8, 9, 15, 16, 23, 31, 40, 47, 5]
    args = _ragged_case(rng, t_rows, h, kvh, d, 4 * max_pages + 8, p,
                        max_pages, qpos)
    _assert_parity(args)


def test_kernel_zero_length_padding_rows_are_exact_zero():
    rng = np.random.default_rng(11)
    t_rows, d, p, max_pages = 8, 128, 8, 4
    qpos = [4, -1, 12, -1, -1, 31, 0, -1]
    args = _ragged_case(rng, t_rows, 4, 2, d, 24, p, max_pages, qpos)
    got = _assert_parity(args)
    for i, qp in enumerate(qpos):
        if qp < 0:
            assert np.all(got[i] == 0.0), f"padding row {i} not zeroed"


def test_kernel_parity_multi_block_grid():
    # max_pages=8 with page_size=8 -> block_choice want=256 tokens ->
    # ppcb=8 ... force a multi-block grid instead by a larger table:
    # max_pages=36, p=8 -> want 256/8=32 pages -> ppcb=36's divisor <=32
    # = 18 -> 2 sequential blocks, exercising the flash accumulator.
    rng = np.random.default_rng(13)
    t_rows, d, p, max_pages = 6, 128, 8, 36
    qpos = [0, 63, 100, 200, 287, -1]
    args = _ragged_case(rng, t_rows, 4, 2, d, 4 * max_pages + 8, p,
                        max_pages, qpos)
    assert 36 // block_choice(36, 8) > 1  # really multi-block
    _assert_parity(args)


def test_ragged_supported_geometry():
    assert ragged_supported(8, 128)
    assert not ragged_supported(8, 64)      # head_dim not lane-aligned
    assert not ragged_supported(4, 128)     # page under sublane tile
    assert not ragged_supported(6, 128)


# ---------------------------------------------------------------------------
# block_choice (shared divisor-scan heuristic)


def test_block_choice_pins_measured_v5e_points():
    # a quarter of the context, at least 256 tokens, a divisor of the
    # table: 36 pages of 32 tokens -> 9 pages/block; 32 of 16 -> 16
    assert block_choice(36, 32) == 9
    assert block_choice(32, 16) == 16


def test_block_choice_matches_inline_scan():
    for max_pages in (1, 2, 3, 8, 12, 16, 27, 32, 36, 64, 100):
        for page_size in (4, 8, 16, 32, 128):
            want_tokens = max(256, (max_pages * page_size) // 4)
            want = max(1, want_tokens // page_size)
            best = 1
            for cand in range(1, max_pages + 1):
                if max_pages % cand == 0 and cand <= want:
                    best = cand
            got = block_choice(max_pages, page_size)
            assert got == best, (max_pages, page_size)
            assert max_pages % got == 0     # must tile the table exactly


# ---------------------------------------------------------------------------
# fallback attribution


def test_fallback_counter_and_reason_on_unaligned_head_dim():
    # Force the kernel path on CPU with head_dim 16: paged_attention_decode
    # must decline to the XLA path and attribute why.
    before = attention.attention_fallbacks.get(reason="head_dim")
    set_attention_impl("pallas")
    try:
        q = jnp.zeros((2, 4, 16), jnp.float32)
        kp = jnp.zeros((2, 8, 4, 16), jnp.float32)
        out = attention.paged_attention_decode(
            q, kp, kp, jnp.asarray([1, 2]), jnp.zeros((2, 4), jnp.int32),
            page_size=4)
        assert out.shape == (2, 4, 16)
    finally:
        set_attention_impl("xla")
    assert attention.attention_fallbacks.get(reason="head_dim") > before


def test_ragged_dispatcher_falls_back_and_counts_ineligible():
    before = attention.attention_fallbacks.get(reason="ragged_ineligible")
    set_attention_impl("pallas")
    try:
        q = jnp.zeros((4, 4, 16), jnp.float32)
        kp = jnp.zeros((2, 8, 4, 16), jnp.float32)
        out = attention.ragged_attention(
            q, kp, kp, jnp.asarray([0, 1, -1, 2]),
            jnp.zeros(4, jnp.int32), jnp.zeros((2, 4), jnp.int32),
            page_size=4)
        assert out.shape == (4, 4, 16)
        assert np.all(np.asarray(out)[2] == 0.0)   # padding row zeroed
    finally:
        set_attention_impl("xla")
    assert attention.attention_fallbacks.get(
        reason="ragged_ineligible") > before


# ---------------------------------------------------------------------------
# e2e engine equivalence (CPU backend; ragged rides the XLA flat path)


def make_engine(**kw):
    defaults = dict(
        model=LlamaConfig.tiny(),
        num_pages=64, max_batch_size=4, prefill_chunk=32,
        min_prefill_bucket=8, default_max_tokens=8,
        decode_steps_per_sync=2, prefill_chunk_budget=12)
    defaults.update(kw)
    return TpuEngine(TpuEngineConfig(**defaults))


def req(tokens, max_tokens=8, temperature=0.0, seed=None):
    return {"token_ids": list(tokens), "model": "m",
            "sampling": {"temperature": temperature, "seed": seed},
            "stop": {"max_tokens": max_tokens}}


async def _drain(engine, request):
    toks = []
    async for o in engine.generate(request, Context()):
        toks.extend(o.get("token_ids", ()))
    return toks


async def _consume(eng, request, label, events):
    toks = []
    async for o in eng.generate(request, Context()):
        if o.get("token_ids"):
            events.append(label)
            toks.extend(o["token_ids"])
    return toks


async def _run_workload(eng):
    """Scripted mixed workload: two short lanes decoding, a long prompt
    landing mid-decode (budgeted chunk rounds → mixed steps), then two
    more prompts with different lengths and a misaligning chunk budget.
    On the legacy path this compiles prefill shapes at two widths,
    mixed-step shapes at two chunk buckets AND both alignment variants,
    plus the fixed decode burst; the ragged path collapses all of it
    onto (t_bucket, tk)."""
    events = []
    shorts = [asyncio.create_task(_consume(
        eng, req(list(range(1 + i, 7 + 2 * i)), 36), f"s{i}", events))
        for i in range(2)]
    while len({lab for lab in events if lab.startswith("s")}) < 2:
        await asyncio.sleep(0.01)
    l0 = asyncio.create_task(_consume(
        eng, req(list(range(3, 43)), 8), "l0", events))
    while "l0" not in events:
        await asyncio.sleep(0.01)
    l1 = asyncio.create_task(_consume(
        eng, req(list(range(5, 28)), 6), "l1", events))
    l2 = asyncio.create_task(_consume(
        eng, req(list(range(7, 24)), 6), "l2", events))
    return await asyncio.gather(*shorts, l0, l1, l2)


async def test_engine_tokens_identical_ragged_on_vs_off():
    set_attention_impl("xla")
    eng = make_engine()
    try:
        base = await _run_workload(eng)
        entries_off = {e for (e, _) in eng.metrics.compile._seen}
        off_total = eng.metrics.compile.total
    finally:
        await eng.close()
    # ragged-off pin: the unarmed serving path never dispatches the
    # ragged entry (byte-identical legacy behaviour)
    assert "ragged_step" not in entries_off

    set_attention_impl("ragged")
    eng = make_engine()
    try:
        rag = await _run_workload(eng)
        entries_on = {e for (e, _) in eng.metrics.compile._seen}
        on_total = eng.metrics.compile.total
        assert eng.ragged_active
    finally:
        await eng.close()
    set_attention_impl("xla")

    assert "ragged_step" in entries_on
    # greedy streams byte-identical: the flat path must not perturb a
    # single token on any lane
    assert rag == base
    # the legacy shape zoo (prefill x (bp, t, aligned), mixed, decode
    # widths) collapses onto (t_bucket,): strict reduction on this
    # scripted mix
    assert on_total < off_total, (on_total, off_total)


async def test_ragged_engine_seeded_sampling_reproducible():
    set_attention_impl("ragged")
    outs = []
    for _ in range(2):
        eng = make_engine()
        try:
            outs.append(await _drain(
                eng, req(range(1, 12), max_tokens=6, temperature=0.8,
                         seed=1234)))
        finally:
            await eng.close()
    set_attention_impl("xla")
    assert outs[0] == outs[1]
    assert len(outs[0]) == 6


def test_ragged_enabled_tracks_impl():
    assert not ragged_enabled()
    set_attention_impl("ragged")
    assert ragged_enabled()
    set_attention_impl("xla")
    assert not ragged_enabled()


# ---------------------------------------------------------------------------
# control handoff + mock cost model


def test_bucket_autotuner_retires_ladder_on_ragged_engine():
    from types import SimpleNamespace

    from dynamo_tpu.control.controllers import BucketAutotuner
    from dynamo_tpu.engine.profiler import StepRecorder

    rec = StepRecorder(capacity=64)
    for _ in range(64):  # padding burn that would normally earn a rung
        rec.record("prefill", (1, 64), 0.01, good_tokens=8,
                   work_tokens=64, lanes=1, width=1)
    eng = SimpleNamespace(step_recorder=rec, bucket_ladder=None,
                          ragged_active=True,
                          config=SimpleNamespace(worker_id=0))
    tuner = BucketAutotuner(lambda: [eng])
    first = tuner.tick(now=0.0)
    assert len(first) == 1
    assert first[0]["to"] == "retired"
    assert "ragged" in first[0]["reason"]
    # the handoff is announced exactly once, then the engine is skipped
    assert tuner.tick(now=1.0) == []
    assert eng.bucket_ladder is None   # no ladder ever installed


def test_mock_ragged_bucket_family():
    from dynamo_tpu.mocker.engine import _ragged_bucket

    # pow2 below the 16-token floor (decode-tail rounds), then the
    # 1.5-step ladder — mirrors TpuEngine._ragged_bucket
    got = [_ragged_bucket(n) for n in (1, 2, 3, 9, 16, 17, 25, 49)]
    assert got == [1, 2, 4, 16, 16, 24, 32, 64]


async def test_mock_engine_ragged_records_flat_entry():
    from dynamo_tpu.engine.profiler import StepRecorder
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig

    eng = MockEngine(MockEngineConfig(ragged=True, speedup=1000.0))
    eng.step_recorder = StepRecorder(capacity=256)
    assert eng.ragged_active
    try:
        outs = [o async for o in eng.generate(
            {"token_ids": list(range(24)), "model": "m",
             "stop": {"max_tokens": 4}}, Context())]
        toks = [t for o in outs for t in o.get("token_ids", ())]
        assert len(toks) == 4
        s = eng.step_recorder.summary()
        assert set(s["entries"]) == {"ragged_step"}
        # analytic padding model: 24 uncached prompt tokens ride bucket
        # 24 exactly (zero padding); each decode round pads 1 lane to
        # the pow2 bucket 1 (zero padding)
        assert s["entries"]["ragged_step"]["padded_tokens"] == 0
    finally:
        await eng.close()
