"""The program's LFM2-MoE family (models/lfm2_moe.py) against the
benchmark's plain reference (`benchmarks/chip/reference/lfm2_moe.py`,
imported as it stands) on a seeded checkpoint the benchmark's own writer
wrote: LOGITS, not tokens. The toy has heads of 64 (two a row of the cache:
`folded`), two dense FFNs before the expert layers, both operators, a tied
head. Prefill whole, in chunks over ragged boundaries (the convolution's
state carried in a slot, taken at the last REAL token) and token by token
through pages and slots, each against the reference's ONE pass with the
convolution as a sum over the sequence; then the engine: lanes joining and
leaving a running batch, a slot's next tenant, int8.

Tolerances as tests/test_nemotron_reference.py: float32 on both sides
differs by the order of the sums, 1e-4 on logits of order 10; int8 weights
on both sides 1e-3; bf16 activations say only that bf16 is bf16."""

import asyncio
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.engine.quant import QTensor, quantize_params
from dynamo_tpu.models import lfm2_moe as lf
from dynamo_tpu.models.loader import config_from_hf, load_llama_params
from tests import lfm2_toy as toy_

set_attention_impl("xla")
F32_TOL = 1e-4
PAGE, SLOT = 8, 2


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("lfm2-toy"))
    toy_.write_checkpoint(path)
    cfg = config_from_hf(path, dtype=jnp.float32, page_size=PAGE,
                         max_pages_per_seq=8)
    return {"path": path, "cfg": cfg, "read": toy_.reader(path),
            "params": load_llama_params(path, cfg)}


def ids_of(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 290, n)]


def reference(toy, ids, config=None):
    """(len(ids) - 1, V): row i predicts token i + 1."""
    return toy_.reference_logits(toy["read"], config or toy_.config_for(),
                                 [ids], [1])[0]


def through_the_program(cfg, params, ids, chunks, decode, slot=SLOT,
                        caches=None):
    """Logits of the positions the program computes: each chunk's last
    token, then `decode` steps teacher-forced with `ids`; [(position,
    logits)]."""
    kc, vc = caches or lf.init_cache(cfg, 10, 4)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    slots = jnp.asarray([slot], jnp.int32)
    out, at = [], 0
    for n in chunks:
        bucket = max(PAGE, -(-n // PAGE) * PAGE)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = ids[at:at + n]
        logits, kc, vc = lf.prefill_batch(
            params, kc, vc, jnp.asarray(toks), table, jnp.asarray([at]),
            jnp.asarray([at + n]), cfg, at % PAGE == 0, slots=slots)
        at += n
        out.append((at - 1, np.asarray(logits[0])))
    z = jnp.zeros(2)
    for _ in range(decode):
        # lane 1 is invalid: slot 0, page table 0
        packed, kc, vc = lf.decode_multi_step(
            params, kc, vc, jnp.asarray([ids[at], 0]), jnp.asarray([at, 0]),
            jnp.concatenate([table, table * 0]),
            jnp.asarray([True, False]), z.astype(jnp.uint32),
            z.astype(jnp.uint32), z, z + 1, z.astype(jnp.int32), cfg, 1,
            topk_lp=4, slots=jnp.asarray([slot, 0]))
        out.append((at, packed))
        at += 1
    return out, (kc, vc)


CHUNKINGS = {
    "one chunk": (24,),
    "two chunks": (16, 8),
    "three, ragged last, padded": (8, 8, 5),
    "a chunk of one token": (16, 1, 7),
    "a chunk of two tokens": (16, 2, 6),
    "short first chunk": (3, 16),
}


@pytest.mark.parametrize("chunks", CHUNKINGS.values(), ids=CHUNKINGS.keys())
def test_prefill_in_chunks_then_decode_through_slots_float32(toy, chunks):
    ids = ids_of(sum(chunks) + 7, seed=len(chunks))
    want = reference(toy, ids)
    got, (kc, vc) = through_the_program(toy["cfg"], toy["params"], ids,
                                        chunks, decode=6)
    for pos, z in got[:len(chunks)]:
        np.testing.assert_allclose(z, want[pos], atol=F32_TOL)
    for pos, packed in got[len(chunks):]:
        # the burst's packed output: chosen id, its log-probability, the
        # four best ids and theirs
        lp = toy_.log_softmax(want[pos])
        assert int(packed[0, 0, 0]) == int(want[pos].argmax())
        np.testing.assert_allclose(packed[1, 0, 0], lp.max(), atol=F32_TOL)
        best = np.argsort(-lp)[:4]
        np.testing.assert_array_equal(packed[2:6, 0, 0].astype(int), best)
        np.testing.assert_allclose(packed[6:10, 0, 0], lp[best],
                                   atol=F32_TOL)
    # scratch slot 0 took the invalid lane and the padding, and is zero;
    # the sequence's slot holds its last two inputs
    for (op, *_), older, newer in zip(toy["cfg"].table, kc, vc):
        if op == "conv":
            assert not np.asarray(older[0]).any()
            assert not np.asarray(newer[0]).any()
            assert np.asarray(older[SLOT]).any()
            assert np.asarray(newer[SLOT]).any()


def test_every_position_in_one_pass_float32(toy):
    ids = ids_of(40, seed=9)
    got = lf.forward_logits(toy["params"], jnp.asarray(ids, jnp.int32),
                            toy["cfg"])
    np.testing.assert_allclose(got[:-1], reference(toy, ids), atol=F32_TOL)


def test_the_state_is_taken_at_the_last_real_token(toy):
    """A chunk of 5 in a bucket of 8: the slot holds g of tokens 3 and 4 of
    the chunk, whatever the three padded positions computed. Prefilled as
    (8, 5) or as (8, 8 with three more real tokens cut off) the state after
    13 tokens is the same."""
    ids = ids_of(20, seed=3)
    _, (k1, v1) = through_the_program(toy["cfg"], toy["params"], ids,
                                      (8, 5), decode=0)
    _, (k2, v2) = through_the_program(toy["cfg"], toy["params"], ids,
                                      (13,), decode=0)
    for (op, *_), a, b, c, d in zip(toy["cfg"].table, k1, k2, v1, v2):
        if op == "conv":
            np.testing.assert_allclose(a[SLOT], b[SLOT], atol=1e-5)
            np.testing.assert_allclose(c[SLOT], d[SLOT], atol=1e-5)


def test_a_slot_reused_by_a_new_sequence_starts_from_zero(toy):
    """The second sequence takes the first one's slot and pages as they
    were left: a first chunk starts from zero whatever its slot holds."""
    first, second = ids_of(30, seed=1), ids_of(30, seed=2)
    _, caches = through_the_program(toy["cfg"], toy["params"], first,
                                    (16, 8), decode=3)
    got, _ = through_the_program(toy["cfg"], toy["params"], second,
                                 (16, 8), decode=0, caches=caches)
    want = reference(toy, second)
    for pos, z in got:
        np.testing.assert_allclose(z, want[pos], atol=F32_TOL)


def test_choice_by_biased_weight_by_unbiased_score(toy):
    """The toy's bias changes some token's choice (else this checks
    nothing), and the weights are the unbiased scores over their sum +
    1e-6: weighting by the biased scores, or choosing without the bias, is
    another model."""
    from dynamo_tpu.models.mixtral import moe_route

    cfg = toy["cfg"]
    moe = toy["params"]["layers"]["moe"]
    lp = {k: moe[k][0] for k in ("router", "router_bias")}
    h = np.random.RandomState(0).standard_normal((64, cfg.hidden_size))
    h = jnp.asarray(h, jnp.float32)
    gates, chosen = moe_route(h, lp, cfg)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(h @ lp["router"], np.float64)))
    biased = scores + np.asarray(lp["router_bias"], np.float64)
    k = cfg.experts_per_token
    want = np.argsort(-biased, axis=-1)[:, :k]
    plain = np.argsort(-scores, axis=-1)[:, :k]
    assert (np.sort(want, -1) != np.sort(plain, -1)).any()
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(
        gates, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    assert cfg.router_norm_eps == 1e-6 and cfg.routed_scaling == 1.0


def test_leading_dense_layers_and_a_tied_head(toy):
    cfg, params = toy["cfg"], toy["params"]
    assert [(op, ffn) for op, _, ffn, _, _ in cfg.table] == [
        ("conv", "dense"), ("conv", "dense"), ("attn", "moe"),
        ("conv", "moe"), ("attn", "moe"), ("conv", "moe")]
    assert params["layers"]["dense"]["w_gate"].shape == (2, 256, 160)
    assert params["layers"]["moe"]["w_gate"].shape == (4, 8, 256, 96)
    np.testing.assert_array_equal(np.asarray(params["lm_head"]),
                                  np.asarray(params["embed"]).T)
    # heads of 64: two kv heads a row of the cache, the model's own bytes
    kc, _ = lf.init_cache(cfg, 5, 3)
    assert (cfg.head_dim, cfg.kv_fold) == (64, 2)
    assert kc[2].shape == (1, 5, PAGE, 128) and kc[0].shape == (3, 256)


def test_int8_weights_as_the_configuration_states_them(toy):
    """`--quantize int8` against the reference at `as_served(w, 8)`: the
    same rounding rule on both sides, float32 activations. int8: in_proj,
    out_proj, q/k/v/out, dense and expert w1/w2/w3, the head; router, its
    bias, taps, norms and embedding untouched."""
    ids = ids_of(30, seed=4)
    params = quantize_params(toy["params"], mode="int8")
    layers = params["layers"]
    quantized = {f"{kind}.{k}" for kind, d in layers.items()
                 for k, w in d.items() if isinstance(w, QTensor)}
    assert quantized == {
        "conv.in_proj", "conv.out_proj", "attn.wq", "attn.wk", "attn.wv",
        "attn.wo", "dense.w_gate", "dense.w_up", "dense.w_down",
        "moe.w_gate", "moe.w_up", "moe.w_down"}
    assert isinstance(params["lm_head"], QTensor)
    assert not isinstance(params["embed"], QTensor)
    got, _ = through_the_program(toy["cfg"], params, ids, (16, 8), decode=0)
    want = reference(toy, ids, toy_.config_for(layers_bytes=1))
    for pos, z in got:
        np.testing.assert_allclose(z, want[pos], atol=1e-3)
    # and the bf16-stated reference is another model: int8 is seen
    assert np.abs(got[-1][1] - reference(toy, ids)[got[-1][0]]).max() > 0.01


def test_the_device_loader_serves_int8_as_quantize_params_does(toy):
    from dynamo_tpu.models.loader import load_llama_params_device

    ids = ids_of(30, seed=6)
    params = load_llama_params_device(toy["path"], toy["cfg"],
                                      quantize="int8")
    got, _ = through_the_program(toy["cfg"], params, ids, (16, 8), decode=0)
    want = reference(toy, ids, toy_.config_for(layers_bytes=1))
    for pos, z in got:
        np.testing.assert_allclose(z, want[pos], atol=1e-3)


def test_bf16_activations_are_bf16(toy):
    """The served precision against the float32 reference: the median
    deviation of a row's log-probabilities says that bf16 is bf16. Not the
    largest: the toy's experts are top-2 of 8 with weights of about a half,
    and where bf16 and float32 routing swap a token's 2nd and 3rd expert a
    whole row moves by several units."""
    ids = ids_of(30, seed=5)
    cfg = dataclasses.replace(toy["cfg"], dtype=jnp.bfloat16)
    params = load_llama_params(toy["path"], cfg)
    got, _ = through_the_program(cfg, params, ids, (16, 8), decode=3)
    want = reference(toy, ids)
    for pos, z in got[:2]:
        assert np.median(np.abs(toy_.log_softmax(z)
                                - toy_.log_softmax(want[pos]))) < 0.5
    devs = [abs(float(packed[1, 0, 0])
                - toy_.log_softmax(want[pos])[int(packed[0, 0, 0])])
            for pos, packed in got[2:]]
    assert np.median(devs) < 0.5


# -- through the engine ------------------------------------------------------


def engine_config(cfg, width=4, **kw):
    cfg = dataclasses.replace(cfg, max_pages_per_seq=16)
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


@pytest.mark.parametrize("n,max_tokens", [(13, 9), (37, 20)])
def test_the_engine_serves_the_references_logits(toy, n, max_tokens):
    """Through admission, slots, chunk rounds (chunk 16: 37 tokens are
    three chunks, the last of 5 in a bucket of 16), the first-token sampler
    and decode bursts: the reported log-probabilities are the reference's,
    and a greedy token is its best."""
    prompt = ids_of(n, seed=n)
    (got,), active = toy_.serve(engine_config(toy["cfg"]), toy["params"],
                                [toy_.request(prompt, max_tokens)])
    toks, lps, _, finish, error = got
    assert (finish, error, active) == ("length", None, 0)
    z = reference(toy, prompt + toks)[n - 1:]
    np.testing.assert_allclose(
        lps, toy_.log_softmax(z)[np.arange(len(toks)), toks], atol=F32_TOL)
    assert (z.max(-1) - z[np.arange(len(toks)), toks]).max() <= F32_TOL


@pytest.mark.parametrize("width", [2, 4])
def test_lanes_joining_and_leaving_give_what_each_gives_alone(toy, one_lane,
                                                              width):
    """Seven requests queue at width 2 and 4: a lane that ends is
    refilled while the others go on, batch rows shift, and a slot gets its
    next tenant."""
    got, active = toy_.serve(engine_config(toy["cfg"], width=width),
                             toy["params"], requests())
    assert active == 0
    for (toks, lps, _, finish, error), alone, req in zip(
            got, one_lane, requests()):
        assert (finish, error) == ("length", None)
        assert len(toks) == req["stop"]["max_tokens"]
        assert toks == alone[0]
        np.testing.assert_allclose(lps, alone[1], atol=5e-5)


def test_slots_are_counted_reset_and_returned(toy, one_lane):
    """One lane, one slot: every request follows the last in the same slot
    and gives what it gives in a fresh engine; the slot counters of PR 41
    and the routed-row counter count for this family too."""
    async def run():
        engine = TpuEngine(engine_config(toy["cfg"], width=1),
                           params=toy["params"])
        try:
            out = []
            for r in requests()[:3]:
                out.append(await toy_.collect(engine, r))
                assert engine.slots.in_use == 0
            m = engine.metrics
            return (out, m.state_resets.get(), m.state_slots.get(),
                    engine._routed_per_token)
        finally:
            await engine.close()

    got, resets, slots, routed = asyncio.run(run())
    assert (resets, slots) == (3, 1)
    assert routed == 2 * 4                  # top-2 x four expert layers
    for mine, alone in zip(got, one_lane):
        assert mine[0] == alone[0]


def test_the_memory_ledger_counts_the_slots(toy):
    from dynamo_tpu.engine.pages import state_slot_bytes

    # four conv layers x two inputs of 256 a slot
    assert state_slot_bytes(toy["cfg"], 4) == 4 * 2 * 256 * 4
    assert state_slot_bytes(toy["cfg"]) == 4 * 2 * 256 * 2


REFUSED_AT_START = [
    (dict(prefill_chunk_budget=16), "prefill_chunk_budget"),
    (dict(dllm_denoising_steps=2), "block diffusion"),
]


@pytest.mark.parametrize("kw,message", REFUSED_AT_START,
                         ids=[m for _, m in REFUSED_AT_START])
def test_refused_at_start_as_every_model_with_state_in_slots(toy, kw,
                                                             message):
    with pytest.raises(ValueError, match=message):
        TpuEngine(engine_config(toy["cfg"], **kw), params=toy["params"])


def test_a_kvbm_tier_and_a_disaggregated_role_are_refused(toy):
    async def run():
        engine = TpuEngine(engine_config(toy["cfg"]), params=toy["params"])
        try:
            for what in ("a KVBM tier", "a disaggregated role"):
                with pytest.raises(ValueError, match="recurrent layers"):
                    engine.refuse_if_recurrent(what)
        finally:
            await engine.close()

    asyncio.run(run())
