"""The program against the benchmark's plain reference
(`benchmarks/chip/reference/nemotron_h.py`, imported as it stands) on a
seeded checkpoint the benchmark's own writer wrote: LOGITS, not tokens.
Prefill in chunks through `prefill_batch` (the chunked recurrence, a state
carried from chunk to chunk in a slot, bucket padding, a ragged last chunk,
chunks shorter than the convolution), then decode steps through the slot
and the pages, against the reference's ONE full forward pass with the
recurrence token by token.

Tolerances. float32 on both sides: the program sums the recurrence in
blocks and the experts over routed rows, the reference token by token and
over all experts; logits of order 10 then differ by float32 rounding: 1e-4.
bf16 activations (the served precision) against the float32 reference read
up to ~0.12 on this toy; the limit is 0.35, three times that, and says only
that bf16 is bf16. int8 weights on both sides (the reference through
`as_served`) in float32 activations: 1e-3, the rounding of the scales'
products."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngineConfig
from dynamo_tpu.engine.quant import quantize_params
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.loader import config_from_hf, load_llama_params
from tests import nemotron_toy as toy_

set_attention_impl("xla")
F32_TOL = 1e-4
PAGE, SLOT = 8, 2


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("nemotron-toy"))
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


def through_the_program(cfg, params, ids, chunks, decode):
    """Logits of the positions the program computes: each chunk's last
    token, then `decode` steps teacher-forced with `ids`; [(position,
    logits)]."""
    kc, vc = nh.init_cache(cfg, 10, 4)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    slots = jnp.asarray([SLOT], jnp.int32)
    out, at = [], 0
    for n in chunks:
        bucket = max(PAGE, -(-n // PAGE) * PAGE)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = ids[at:at + n]
        logits, kc, vc = nh.prefill_batch(
            params, kc, vc, jnp.asarray(toks), table, jnp.asarray([at]),
            jnp.asarray([at + n]), cfg, at % PAGE == 0, slots=slots)
        at += n
        out.append((at - 1, np.asarray(logits[0])))
    z = jnp.zeros(2)
    for _ in range(decode):
        # lane 1 is invalid: slot 0, page table 0
        packed, kc, vc = nh.decode_multi_step(
            params, kc, vc, jnp.asarray([ids[at], 0]), jnp.asarray([at, 0]),
            jnp.concatenate([table, table * 0]),
            jnp.asarray([True, False]), z.astype(jnp.uint32),
            z.astype(jnp.uint32), z, z + 1, z.astype(jnp.int32), cfg, 1,
            topk_lp=4, slots=jnp.asarray([SLOT, 0]))
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
    # scratch slot 0 took the invalid lane and the padding, and is zero
    for pair in zip(kc, vc):
        if pair[1].ndim == 4 and pair[1].dtype == jnp.float32 \
                and pair[1].shape[0] == 4:
            np.testing.assert_array_equal(pair[1][0], 0.0)


def test_every_position_in_one_pass_float32(toy):
    ids = ids_of(40, seed=9)
    got = nh.forward_logits(toy["params"], jnp.asarray(ids, jnp.int32),
                            toy["cfg"])
    np.testing.assert_allclose(got[:-1], reference(toy, ids), atol=F32_TOL)


def test_int8_weights_as_the_configuration_states_them(toy):
    """`--quantize int8` against the reference at `as_served(w, 8)`: the
    same rounding rule on both sides, float32 activations."""
    ids = ids_of(30, seed=4)
    params = quantize_params(toy["params"], mode="int8")
    got, _ = through_the_program(toy["cfg"], params, ids, (16, 8), decode=0)
    want = reference(toy, ids, toy_.config_for(layers_bytes=1))
    for pos, z in got:
        np.testing.assert_allclose(z, want[pos], atol=1e-3)
    # and the bf16-stated reference is another model: int8 is seen
    assert np.abs(got[-1][1] - reference(toy, ids)[got[-1][0]]).max() > 0.01


def test_bf16_activations_are_bf16(toy):
    import dataclasses

    ids = ids_of(30, seed=5)
    cfg = dataclasses.replace(toy["cfg"], dtype=jnp.bfloat16)
    params = load_llama_params(toy["path"], cfg)
    got, _ = through_the_program(cfg, params, ids, (16, 8), decode=3)
    want = reference(toy, ids)
    for pos, z in got[:2]:
        assert np.abs(toy_.log_softmax(z) - toy_.log_softmax(want[pos])
                      ).max() < 0.35
    for pos, packed in got[2:]:
        lp = toy_.log_softmax(want[pos])
        assert abs(float(packed[1, 0, 0]) - lp[int(packed[0, 0, 0])]) < 0.35


def engine_config(cfg, width=4):
    import dataclasses

    cfg = dataclasses.replace(cfg, max_pages_per_seq=16)
    return TpuEngineConfig(model=cfg, num_pages=64, max_batch_size=width,
                           prefill_chunk=16, decode_steps_per_sync=8)


@pytest.mark.parametrize("n,max_tokens", [(13, 9), (16, 12), (37, 20)])
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
