"""The engine end to end on the toy Nemotron-H: state slots beside the
page pool. Tokens must not depend on the batch width while other sequences
end and rows shift, a slot's next tenant must see zero state, every slot
must come back, prefix reuse is off, and every combination that does not
know of the state is refused with its message. float32: tokens are compared
exactly, log-probabilities at 5e-5 (the sums of a wider batch round
differently)."""

import asyncio
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.loader import config_from_hf, load_llama_params
from dynamo_tpu.runtime.context import Context
from tests import nemotron_toy as toy_

set_attention_impl("xla")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("nemotron-toy"))
    toy_.write_checkpoint(path)
    cfg = config_from_hf(path, dtype=jnp.float32, page_size=8,
                         max_pages_per_seq=16)
    return {"path": path, "cfg": cfg,
            "params": load_llama_params(path, cfg)}


def engine_config(cfg, width=4, **kw):
    kw = {"num_pages": 96, "prefill_chunk": 16, **kw}
    return TpuEngineConfig(model=cfg, max_batch_size=width,
                           decode_steps_per_sync=8, **kw)


def requests():
    """Lengths that end at different bursts, so rows shift under the
    sequences that go on; prompts of one, two and three chunks."""
    rs = np.random.RandomState(1)
    lengths = [(13, 9), (8, 30), (3, 5), (22, 16), (17, 1), (37, 24),
               (16, 40)]
    return [toy_.request([int(t) for t in rs.randint(0, 290, n)], m)
            for n, m in lengths]


@pytest.fixture(scope="module")
def one_lane(toy):
    """Every request alone in a one-lane engine."""
    return [toy_.serve(engine_config(toy["cfg"], width=1), toy["params"],
                       [r])[0][0] for r in requests()]


@pytest.mark.parametrize("width", [2, 4, 8])
def test_tokens_do_not_depend_on_the_batch_width(toy, one_lane, width):
    """At width 2 and 4 the seven requests queue: a lane that ends is
    refilled, batch rows shift, and a slot gets its next tenant."""
    got, active = toy_.serve(engine_config(toy["cfg"], width=width),
                             toy["params"], requests())
    assert active == 0
    for (toks, lps, _, finish, error), alone, req in zip(
            got, one_lane, requests()):
        assert (finish, error) == ("length", None)
        assert len(toks) == req["stop"]["max_tokens"]
        assert toks == alone[0]
        np.testing.assert_allclose(lps, alone[1], atol=5e-5)


def test_a_slots_next_tenant_sees_zero_state(toy, one_lane):
    """One lane, one slot: every request follows the last in the same slot
    and gives what it gives in a fresh engine."""
    async def run():
        engine = TpuEngine(engine_config(toy["cfg"], width=1),
                           params=toy["params"])
        try:
            out = []
            for r in requests()[:4]:
                out.append(await toy_.collect(engine, r))
                assert engine.slots.in_use == 0
            return out, engine.metrics.state_resets.get()
        finally:
            await engine.close()

    got, resets = asyncio.run(run())
    assert resets == 4                      # one first chunk a request
    for mine, alone in zip(got, one_lane):
        assert mine[0] == alone[0]


def test_the_same_prompt_twice_prefills_all_of_it_twice(toy):
    """Prefix reuse is off: no page is registered, no KV event goes out,
    and the second request prefills every token again."""
    events = []
    req = requests()[5]                                 # 37 tokens

    async def run():
        engine = TpuEngine(engine_config(toy["cfg"]), params=toy["params"],
                           event_sink=events.append)
        try:
            first = await toy_.collect(engine, req)
            second = await toy_.collect(engine, req)
            return (first, second,
                    engine.metrics.prefill_new_tokens.get(),
                    engine.pool.active_pages, engine.clear_kv_blocks())
        finally:
            await engine.close()

    first, second, prefilled, active, dropped = asyncio.run(run())
    assert first[0] == second[0] and first[3] == "length"
    assert prefilled == 2 * 37
    assert (active, dropped, events) == (0, 0, [])


def test_slots_return_after_finish_cancel_and_preemption(toy):
    async def run():
        # 15 pages: three sequences of 40+ tokens cannot all grow, so the
        # youngest is preempted and prefilled again from nothing
        engine = TpuEngine(engine_config(toy["cfg"], width=3, num_pages=16,
                                         watermark=1.0),
                           params=toy["params"])
        assert engine.metrics.state_slots.get() == 3
        try:
            ctx = Context()
            rs = np.random.RandomState(2)
            reqs = [toy_.request([int(t) for t in rs.randint(0, 290, 20)],
                                 40) for _ in range(3)]

            async def cancelled():
                toks = []
                async for out in engine.generate(reqs[0], ctx):
                    toks += out.get("token_ids") or []
                    if len(toks) >= 4:
                        ctx.cancel()
                return toks

            got = await asyncio.gather(
                cancelled(), toy_.collect(engine, reqs[1]),
                toy_.collect(engine, reqs[2]))
            alone = [await toy_.collect(engine, r) for r in reqs[1:]]
            return (got, alone, engine.slots.in_use,
                    engine.pool.active_pages,
                    engine.metrics.state_slots_in_use.get(),
                    engine.metrics.state_resets.get())
        finally:
            await engine.close()

    got, alone, in_use, active, gauge, resets = asyncio.run(run())
    assert (in_use, active, gauge) == (0, 0, 0)
    assert got[1][3] == got[2][3] == "length"
    # preempted or not, the tokens are those of the sequence alone
    assert got[1][0] == alone[0][0] and got[2][0] == alone[1][0]
    assert resets >= 5                        # 3 + 2 alone (+ re-prefills)


def test_a_preempted_sequence_is_prefilled_again_from_zero(toy):
    """Preemption returns the slot; the re-prefill of prompt + generated
    starts from zero in whatever slot it then gets."""
    async def run():
        engine = TpuEngine(engine_config(toy["cfg"], width=2),
                           params=toy["params"])
        try:
            req = requests()[3]
            it = engine.generate(req, Context())
            toks = []
            async for out in it:
                toks += out.get("token_ids") or []
                if len(toks) == 9 and engine._running:
                    await asyncio.sleep(0)
                    async with engine._device_lock:
                        engine._drain_inflight_sync()
                        engine._preempt(engine._running[0])
                    assert engine.slots.in_use == 0
                if out.get("finish_reason"):
                    break
            return toks, engine.slots.in_use
        finally:
            await engine.close()

    toks, in_use = asyncio.run(run())
    alone = toy_.serve(engine_config(toy["cfg"], width=1), toy["params"],
                       [requests()[3]])[0][0]
    assert in_use == 0
    assert toks == alone[0]


def test_the_memory_ledger_counts_the_state(toy, monkeypatch):
    from dynamo_tpu.engine.pages import state_slot_bytes

    monkeypatch.setenv("DYN_MEM_LEDGER", "1")
    engine = TpuEngine(engine_config(toy["cfg"], width=4),
                       params=toy["params"])
    classes = engine.memory_ledger.summary()["last"]["classes"] \
        if engine.memory_ledger.summary().get("last") else None
    want = 5 * state_slot_bytes(toy["cfg"], 4)
    assert want == 5 * 3 * (3 * 128 * 4 + 4 * 16 * 16 * 4)
    total = sum(a.nbytes for a in engine.k_cache + engine.v_cache)
    kv = 2 * 2 * 96 * 8 * 16 * 4                    # one attention layer
    assert total == want + kv
    if classes is not None:
        assert classes["state_slots"] == want and classes["kv_pool"] == kv


REFUSED_AT_START = [
    (dict(prefill_chunk_budget=32), "prefill_chunk_budget"),
    (dict(dllm_denoising_steps=4), "block diffusion"),
    (dict(quantize="int4"), "weight-only int8"),
]


@pytest.mark.parametrize("kw,message", REFUSED_AT_START,
                         ids=[m for _, m in REFUSED_AT_START])
def test_refused_at_start(toy, kw, message):
    config = dataclasses.replace(engine_config(toy["cfg"]), **kw)
    with pytest.raises(ValueError, match=message):
        TpuEngine(config, params=toy["params"])


@pytest.mark.parametrize("axes", [("tp",), ("ep",), ("pp",), ("sp",)])
def test_meshes_are_refused(toy, cpu_mesh_devices, axes):
    import jax

    mesh = jax.sharding.Mesh(np.asarray(cpu_mesh_devices[:2]), axes)
    kw = {"pp": dict(pp_mesh=mesh), "sp": dict(sp_mesh=mesh,
                                               sp_threshold=8)}.get(
        axes[0], dict(mesh=mesh))
    with pytest.raises(ValueError, match="served on one device"):
        TpuEngine(dataclasses.replace(engine_config(toy["cfg"]), **kw),
                  params=toy["params"])


def test_a_draft_model_is_refused(toy):
    from dynamo_tpu.models.llama import LlamaConfig

    draft = LlamaConfig.tiny(page_size=8, max_pages_per_seq=16)
    with pytest.raises(ValueError, match="draft model"):
        TpuEngine(dataclasses.replace(engine_config(toy["cfg"]),
                                      draft_model=draft),
                  params=toy["params"])


def test_the_ragged_path_is_refused(toy):
    set_attention_impl("ragged")
    try:
        with pytest.raises(ValueError, match="ragged"):
            TpuEngine(engine_config(toy["cfg"]), params=toy["params"])
    finally:
        set_attention_impl("xla")


def test_a_kvbm_tier_and_a_disaggregated_role_are_refused(toy):
    from dynamo_tpu.kvbm import KvbmConfig, KvbmManager

    engine = TpuEngine(engine_config(toy["cfg"]), params=toy["params"])
    with pytest.raises(ValueError, match="a KVBM tier"):
        KvbmManager(engine, KvbmConfig(host_blocks=8))
    assert engine.kvbm is None
    with pytest.raises(ValueError, match="disaggregated role"):
        engine.refuse_if_recurrent(
            "a disaggregated role (a KV export or import)")


REFUSED_REQUESTS = [
    (dict(sampling={"guided": {"regex": "[a-z]+"}}), "guided decoding"),
    (dict(sampling={"min_p": 0.2}), "min_p and sampling penalties"),
    (dict(sampling={"presence_penalty": 0.5}),
     "min_p and sampling penalties"),
    (dict(kv_transfer_params={"do_remote_decode": True}),
     "a KV import or export"),
]


@pytest.mark.parametrize("extra,message", REFUSED_REQUESTS,
                         ids=["guided", "min_p", "penalty", "kv-export"])
def test_refused_per_request(toy, extra, message):
    good = requests()[2]
    bad = toy_.request(good["token_ids"], 5, **extra.get("sampling", {}))
    bad.update({k: v for k, v in extra.items() if k != "sampling"})
    got, active = toy_.serve(engine_config(toy["cfg"]), toy["params"],
                             [bad, good])
    assert got[0][3] == "error" and message in got[0][4]
    assert got[0][0] == []
    # the engine goes on serving, with alternatives too
    assert got[1][3] == "length" and len(got[1][1]) == 5 and active == 0


def test_top_logprobs_are_served(toy):
    req = toy_.request(requests()[0]["token_ids"], 6, top_logprobs=3)

    async def run():
        engine = TpuEngine(engine_config(toy["cfg"]), params=toy["params"])
        try:
            tops = []
            async for out in engine.generate(req, Context()):
                tops += out.get("top_logprobs") or []
            return tops
        finally:
            await engine.close()

    tops = asyncio.run(run())
    assert len(tops) == 6 and all(len(t) == 3 for t in tops)


def test_a_dense_engine_imports_no_nemotron_module(toy):
    """The entries are picked by the configuration's class, and a dense
    worker's start imports nothing of this family."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from dynamo_tpu.engine.engine import TpuEngine\n"
            "e = TpuEngine()\n"
            "assert not e.recurrent and e.slots is None\n"
            "assert 'dynamo_tpu.models.nemotron_h' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})
