"""The program against the benchmark's plain reference
(`benchmarks/chip/reference/sdar_moe.py`, imported as it stands) on seeded
random weights: block-causal prefill, then block steps through the paged
cache, must give the reference's logits for every emitted position.

Tolerances. float32 weights and activations on both sides: the program
sums attention over pages and the experts over routed rows, the reference
over one dense sequence and over all experts, so the logits differ by
float32 rounding of sums of order 1 - 10: 1e-4. bf16 activations (the served
precision) against the float32 reference read up to ~0.05 on this toy; its
limit is 0.15, three times that, and says only that bf16 is bf16.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngineConfig
from dynamo_tpu.models import llama
from dynamo_tpu.models.loader import config_from_hf, load_llama_params
from tests import sdar_toy
from tests.sdar_toy import BLOCK, MASK_ID

set_attention_impl("xla")
F32_TOL = 1e-4


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sdar-toy"))
    sdar_toy.write_checkpoint(path)
    cfg = config_from_hf(path, dtype=jnp.float32, attn_block=BLOCK,
                         page_size=8, max_pages_per_seq=16)
    return {"path": path, "cfg": cfg, "read": sdar_toy.reader(path),
            "params": load_llama_params(path, cfg)}


def engine_config(cfg, steps=4, strategy="sequential", width=2):
    return TpuEngineConfig(
        model=cfg, num_pages=48, max_batch_size=width, prefill_chunk=32,
        decode_steps_per_sync=8, dllm_denoising_steps=steps,
        dllm_unmasking_strategy=strategy)


def prompt_of(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 290, n)]


def check_against_reference(toy, config, prompt, toks, lps, greedy,
                            tol=F32_TOL):
    z = sdar_toy.reference_logits(toy["read"], config, [prompt + toks],
                                  [len(prompt)])[0]
    want = sdar_toy.log_softmax(z)[np.arange(len(toks)), toks]
    np.testing.assert_allclose(lps, want, atol=tol)
    if greedy:
        gap = z.max(axis=-1) - z[np.arange(len(toks)), toks]
        assert gap.max() <= tol, gap
    return z


def test_loader_reads_the_checkpoint_the_benchmark_writes(toy):
    cfg, layers = toy["cfg"], toy["params"]["layers"]
    assert (cfg.num_experts, cfg.experts_per_token) == (8, 2)
    assert cfg.qk_norm and cfg.mask_token_id == MASK_ID
    assert cfg.intermediate_size == 96          # the experts' width
    assert layers["w_gate"].shape == (2, 8, 64, 96)
    assert layers["q_norm"].shape == (2, 16)
    want = toy["read"].numpy(
        "model.layers.1.mlp.experts.5.down_proj.weight")
    np.testing.assert_array_equal(
        np.asarray(layers["w_down"][1, 5], np.float32),
        np.asarray(want, np.float32).T)


# n % B of the prompt: 0 (first block all masked), 1, 3 (one masked);
# max_tokens 10 ends inside a block, whose tail is discarded
@pytest.mark.parametrize("strategy", ["sequential", "low_confidence_static"])
@pytest.mark.parametrize("steps", [4, 2])
@pytest.mark.parametrize("n_mod", [0, 1, 3])
def test_served_tokens_equal_reference(toy, n_mod, steps, strategy):
    prompt = prompt_of(12 + n_mod, seed=n_mod)
    got, active = sdar_toy.serve(
        engine_config(toy["cfg"], steps, strategy), toy["params"],
        [sdar_toy.request(prompt, 10)])
    toks, lps, frames, finish, error = got[0]
    assert (len(toks), finish, error, active) == (10, "length", None, 0)
    assert frames == 2                  # a frame carries a burst's tokens
    check_against_reference(toy, sdar_toy.config_for(steps, strategy),
                            prompt, toks, lps, greedy=True)


@pytest.mark.parametrize("strategy", ["sequential", "low_confidence_static"])
def test_seeded_sampling_equals_reference(toy, strategy):
    prompt = prompt_of(14, seed=5)
    req = sdar_toy.request(prompt, 12, temperature=1.5, seed=7, top_k=20)
    config = engine_config(toy["cfg"], 4, strategy)
    (first, _), (again, _) = (sdar_toy.serve(config, toy["params"], [req])
                              for _ in range(2))
    toks, lps = first[0][:2]
    assert toks == again[0][0]                  # the seed decides the draws
    greedy = sdar_toy.serve(config, toy["params"],
                            [sdar_toy.request(prompt, 12)])[0][0][0]
    assert toks != greedy
    # the reported log-probability is of the UNTEMPERED logits
    check_against_reference(toy, sdar_toy.config_for(4, strategy), prompt,
                            toks, lps, greedy=False)


def test_a_served_token_that_equals_the_mask_id(toy):
    """Whether a position is masked is a boolean the program carries,
    never read off the id: with the mask id's own column of the head
    scaled up the model serves that id, and the positions that hold it are
    still fixed positions."""
    prompt = prompt_of(13, seed=3)
    config = engine_config(toy["cfg"])
    plain, _ = sdar_toy.serve(config, toy["params"],
                              [sdar_toy.request(prompt, 16)])
    winner = plain[0][0][3]         # the mask id takes this token's place
    params = dict(toy["params"])
    head = np.array(params["lm_head"])
    head[:, MASK_ID] = 1.5 * head[:, winner]
    params["lm_head"] = head

    def read(name):
        w = toy["read"](name)
        return w.at[MASK_ID].set(1.5 * w[winner]) \
            if name == "lm_head.weight" else w

    read.numpy = toy["read"].numpy
    got, _ = sdar_toy.serve(config, params, [sdar_toy.request(prompt, 16)])
    toks, lps = got[0][:2]
    # blocks that hold the mask id as a FIXED token go on to be committed
    # and attended by later blocks
    assert MASK_ID in toks[:8] and toks != plain[0][0]
    check_against_reference({**toy, "read": read}, sdar_toy.config_for(),
                            prompt, toks, lps, greedy=True)


def denoise_through_the_cache(toy, prompt, n_blocks, steps, strategy):
    """The block steps by hand, through the program's paged forward: the
    logits of every step (not only the chosen token's), and the order in
    which positions were fixed."""
    cfg, params = toy["cfg"], jax.device_put(toy["params"])
    kc, vc = llama.init_cache(cfg, 16)
    whole = len(prompt) - len(prompt) % BLOCK
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :whole] = prompt[:whole]
    _, kc, vc = llama.prefill_batch(
        params, kc, vc, jnp.asarray(tokens), table, jnp.asarray([0]),
        jnp.asarray([whole]), cfg)
    valid = jnp.asarray([True])
    known = list(prompt[whole:])
    rows, order, ids_out = {}, [], []
    for b in range(n_blocks):
        pos0 = whole + b * BLOCK
        ids = np.full((1, BLOCK), MASK_ID, np.int32)
        ids[0, :len(known)] = known
        masked = np.arange(BLOCK)[None] >= len(known)
        for _ in range(steps):
            z, kc, vc = llama._block_forward(
                params, kc, vc, jnp.asarray(ids), jnp.asarray([pos0]),
                table, valid, cfg, head=True)
            z = np.asarray(z)
            conf = sdar_toy.log_softmax(z[0]).max(axis=-1)[None]
            fix = np.asarray(llama.unmask_choice(
                jnp.asarray(masked), jnp.asarray(conf), BLOCK // steps,
                strategy))
            for j in np.flatnonzero(fix[0]):
                rows[pos0 + j] = z[0, j]
                ids[0, j] = int(z[0, j].argmax())
                order.append(pos0 + int(j))
            masked &= ~fix
        _, kc, vc = llama._block_forward(
            params, kc, vc, jnp.asarray(ids), jnp.asarray([pos0]), table,
            valid, cfg, head=False)
        ids_out += [int(t) for t in ids[0]]
        known = []
    served = ids_out[len(prompt) - whole:]
    return served, np.stack([rows[p] for p in sorted(rows)]), order


@pytest.mark.parametrize("steps", [4, 2])
def test_the_burst_fixes_the_positions_max_log_softmax_would(toy, steps):
    """`low_confidence_static` reads a row's confidence off the sampler's
    greedy pair (the log-probability of the row's best token IS the chosen
    token's) since PR 49. The burst itself against the steps by hand, whose
    confidence is the formulation it had, max(log_softmax): the same ids,
    and each with the log-probability of the step that fixed it, which
    names the step (a position's logits move from step to step)."""
    prompt = prompt_of(13, seed=9)
    n_blocks = 3
    served, z, order = denoise_through_the_cache(
        toy, prompt, n_blocks, steps, "low_confidence_static")
    assert order != sorted(order)               # not left to right
    cfg, params = toy["cfg"], jax.device_put(toy["params"])
    kc, vc = llama.init_cache(cfg, 16)
    whole = len(prompt) - len(prompt) % BLOCK
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :whole] = prompt[:whole]
    _, kc, vc = llama.prefill_batch(
        params, kc, vc, jnp.asarray(tokens), table, jnp.asarray([0]),
        jnp.asarray([whole]), cfg)
    tail = prompt[whole:]
    given = np.zeros((1, BLOCK), np.int32)
    given[0, :len(tail)] = tail
    one = jnp.ones((1,), jnp.float32)
    packed, _, _ = llama.block_decode_multi_step(
        params, kc, vc, jnp.asarray(given), jnp.asarray([len(tail)]),
        jnp.asarray([whole]), table, jnp.asarray([True]),
        jnp.zeros((1,), jnp.uint32), 0 * one, one,
        jnp.zeros((1,), jnp.int32), cfg, n_blocks, steps,
        "low_confidence_static")
    packed = np.asarray(packed)[:, len(tail):, 0]
    assert [int(t) for t in packed[0]] == served
    want = sdar_toy.log_softmax(z)[np.arange(len(served)), served]
    np.testing.assert_allclose(packed[1], want, atol=F32_TOL)


@pytest.mark.parametrize("steps,strategy", [
    (4, "sequential"), (2, "sequential"), (4, "low_confidence_static"),
    (2, "low_confidence_static")])
def test_logits_of_every_emitted_position(toy, monkeypatch, steps, strategy):
    prompt = prompt_of(13, seed=9)
    served, z, order = denoise_through_the_cache(toy, prompt, 3, steps,
                                                 strategy)
    config = sdar_toy.config_for(steps, strategy)
    from lib import family

    ref = family.load("reference", config)
    ref_order, gaps = [], []
    choose = ref._most_confident

    def spy(s, want, logits):
        conf = sdar_toy.log_softmax(logits).max(axis=-1)
        for i in range(len(s.known)):
            mine = sorted(conf[j] for j, w in enumerate(want) if w[2] == i)
            gaps.extend(np.diff(mine))
        fixed = choose(s, want, logits)
        ref_order.extend(p for _, p in fixed)
        return fixed

    monkeypatch.setattr(ref, "_most_confident", spy)
    from lib import refio

    want = ref.logits(toy["read"], config, [prompt + served], [len(prompt)],
                      refio.bits_of(config, None))[0]
    np.testing.assert_allclose(z, want, atol=F32_TOL)
    if strategy == "low_confidence_static":
        # the seed keeps every step's confidences apart, so the order is
        # decided by the weights and not by rounding, and it is the same
        assert min(gaps) > 1e-3, min(gaps)

        def by_block(positions):
            """a block's positions, a step's as a set"""
            per = BLOCK // steps
            blocks = [[p for p in positions if p // BLOCK == b]
                      for b in range(3, 6)]
            return [[sorted(blk[i:i + per]) for i in range(0, len(blk), per)]
                    for blk in blocks]

        # (the reference goes step by step over all blocks, the program
        # block by block: within a block the order must be the same)
        assert by_block(ref_order) == by_block(order)
        assert order != sorted(order)       # and it is not left to right
    else:
        assert order == sorted(order)


def test_bf16_activations_stay_near_the_float32_reference(toy):
    cfg = dataclasses.replace(toy["cfg"], dtype=jnp.bfloat16)
    params = load_llama_params(toy["path"], cfg)
    prompt = prompt_of(14, seed=2)
    got, _ = sdar_toy.serve(engine_config(cfg), params,
                            [sdar_toy.request(prompt, 12)])
    toks, lps = got[0][:2]
    check_against_reference(toy, sdar_toy.config_for(), prompt, toks, lps,
                            greedy=True, tol=0.15)
