"""Batched token sampling, jitted: greedy / temperature / top-k / top-p.

All knobs are per-request arrays so one compiled function serves a mixed
batch (no recompile per sampling config — XLA static-shape friendly).
Randomness is derived *inside* the jit from (seed, step) pairs, so the
scheduler passes plain integers and replay/migration is deterministic.

TPU note: full-vocab `sort` costs tens of ms; instead `lax.top_k` keeps the
MAX_CANDIDATES highest logits and top-k/top-p/sampling run on that
truncated set. User top_k is clipped to MAX_CANDIDATES; top-p mass is
computed over the candidates (the tail beyond 64 candidates carries
negligible probability for real models). Greedy uses a full argmax.

What a batch pays (one TPU v5e, (128, 131072) f32 logits, measured alone
on the chip for PR 44): the top-k is linear in rows x vocabulary and is
NOT cheap at a wide batch of a large vocabulary: 3.50 ms of a sampler's
3.82, next to 0.22 for the argmax and 0.32 for the chosen token's
log-probability. So `sample_tokens_traced` builds the candidate set under
a `lax.cond` on "does any lane of this batch draw": an all-greedy batch
runs the argmax alone (0.3-0.5 ms with its log-probability), a batch in
which one lane draws runs the whole of it for every lane as before
(3.77 ms): the same operations in the same order, so on one compiled
program its tokens are what they were (on the chip a drawn stream still
moves whenever XLA compiles the program around the sampler anew: PERF.md
§6, PR 44). Every sampling site (the fused decode loops, the block burst,
the first-token sampler) takes the condition from here; none keeps one of
its own.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
MAX_CANDIDATES = 64


def _candidate_mask(logits: jax.Array, temperature: jax.Array,
                    top_p: jax.Array, top_k: jax.Array,
                    min_p: Optional[jax.Array] = None):
    """THE filter definition (top-k → top-p → min_p over the sorted
    candidate set): returns (masked_cand_logits (B, C), cand_idx (B, C),
    t (B,)). Shared by the sampler and by speculative decoding's
    filtered-distribution rejection test so the two can never diverge."""
    b, v = logits.shape
    c = min(MAX_CANDIDATES, v)
    cand_logits, cand_idx = lax.top_k(logits, c)           # (B, C) sorted desc

    # user top-k within the candidate set
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, c), 1, c)
    pos = jnp.arange(c)
    masked = jnp.where(pos[None, :] < k_eff[:, None], cand_logits, _NEG_INF)

    # top-p: smallest prefix of the sorted candidates covering the mass.
    # `<=` (not `<`) so top_p=0.0 still keeps index 0 (near-greedy), never
    # an all-masked row that categorical() would sample uniformly from.
    t = jnp.where(temperature > 0, temperature, 1.0)
    probs = jax.nn.softmax(masked / t[:, None], axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) <= top_p[:, None]                 # always keeps [0]
    if min_p is not None:
        # candidates are sorted desc, so probs[:, :1] is the max; index 0
        # always survives (p >= min_p * p for min_p <= 1)
        keep &= probs >= jnp.clip(min_p, 0.0, 1.0)[:, None] * probs[:, :1]
    return jnp.where(keep, masked, _NEG_INF), cand_idx, t


def filtered_probs(logits: jax.Array, temperature: jax.Array,
                   top_p: jax.Array, top_k: jax.Array,
                   min_p: Optional[jax.Array] = None) -> jax.Array:
    """(B, V) probabilities of the ACTUAL sampling distribution: the
    temperature-scaled softmax restricted to the kept candidate set
    (zeros elsewhere); temperature==0 rows are the one-hot argmax.
    This is what speculative decoding's ratio test must use — filtering
    target and draft identically preserves Leviathan correctness, and
    the greedy case needs no special-casing (one-hot dists make the
    test exact argmax equality)."""
    b, v = logits.shape
    masked, cand_idx, t = _candidate_mask(logits, temperature, top_p,
                                          top_k, min_p)
    cand_p = jax.nn.softmax(masked / t[:, None], axis=-1)  # (B, C)
    hard = jax.nn.one_hot(jnp.argmax(masked, axis=-1), masked.shape[-1],
                          dtype=jnp.float32)
    cand_p = jnp.where((temperature > 0)[:, None], cand_p, hard)
    full = jnp.zeros((b, v), jnp.float32)
    return full.at[jnp.arange(b)[:, None], cand_idx].add(cand_p)


def sample_tokens_traced(logits: jax.Array, seeds: jax.Array,
                         steps: jax.Array, temperature: jax.Array,
                         top_p: jax.Array, top_k: jax.Array,
                         min_p: Optional[jax.Array] = None) -> jax.Array:
    """logits: (B, V) fp32; seeds/steps: (B,) u32/i32; temperature/top_p:
    (B,) f32; top_k: (B,) i32 (0 = disabled); min_p: (B,) f32 (0 =
    disabled) — drops candidates whose probability is below
    min_p × max-probability (after temperature). temperature <= 0 ⇒
    greedy. Returns (B,) i32 tokens. Traceable (used inside fused decode
    loops)."""
    def greedy():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        masked, cand_idx, t = _candidate_mask(logits, temperature, top_p,
                                              top_k, min_p)

        def sample_one(seed, step, lg, tt):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            return jax.random.categorical(key, lg / tt)

        choice = jax.vmap(sample_one)(
            seeds.astype(jnp.uint32), steps.astype(jnp.uint32), masked, t)
        sampled = jnp.take_along_axis(cand_idx, choice[:, None],
                                      axis=-1)[:, 0]
        return jnp.where(temperature > 0, sampled, greedy())

    # the candidate set only where some lane of the batch draws: a greedy
    # batch is the argmax it always was (the predicate is loop-invariant
    # in the fused loops, and XLA hoists it). The argmax is taken inside
    # each branch and not ahead of the condition: ahead of it the block
    # burst's head wrote its 256 x 151936 logits as f32 where it writes
    # bf16 (+78 MB of temporaries compiled for a v5e, +1.8% TPOT in the
    # SDAR cell, PR 44)
    return lax.cond(jnp.any(temperature > 0), draw, greedy)


sample_tokens = jax.jit(sample_tokens_traced)


def apply_penalties(logits: jax.Array, prompt_counts: jax.Array,
                    out_counts: jax.Array, repetition: jax.Array,
                    frequency: jax.Array, presence: jax.Array
                    ) -> jax.Array:
    """OpenAI/HF sampling penalties, traceable (fused decode loops).

    logits: (B, V) f32. prompt_counts/out_counts: (B, V) — token
    occurrence counts in the prompt / generated output. Semantics match
    vLLM: repetition_penalty (HF) applies to prompt+output tokens
    (divide positive logits, multiply negative); frequency/presence
    (OpenAI) apply to OUTPUT tokens only, additively."""
    seen = (prompt_counts + out_counts) > 0
    rep = repetition[:, None]
    rep_adj = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen & (rep != 1.0), rep_adj, logits)
    logits = logits - frequency[:, None] * out_counts.astype(logits.dtype)
    logits = logits - presence[:, None] * (out_counts > 0).astype(
        logits.dtype)
    return logits


def stop_token_mask(stop_ids: jax.Array, vocab: int) -> jax.Array:
    """(B, V) bool from (B, K) per-lane stop-token ids (-1 padding):
    which vocab entries are the lane's stop tokens. Shared by every
    guided consumer so 'what counts as a stop token' can't diverge."""
    return (jnp.arange(vocab, dtype=jnp.int32)[None, None, :]
            == stop_ids[:, :, None]).any(axis=1)


def guided_allow(g_bits: jax.Array, g_eos_ok: jax.Array,
                 g_ids: jax.Array, states: jax.Array,
                 is_stop: jax.Array) -> jax.Array:
    """(B, V) bool allow-mask from the stacked DFA tables — THE one
    definition of 'which tokens the grammar permits here' (bit-packed
    allowed rows, plus the lane's stop tokens wherever the grammar
    accepts). Used by the plain constrained burst
    (llama.decode_multi_step_guided), the spec burst (engine/spec.py),
    and the pp constrained head (llama_pp.py) — keeping them
    semantically identical is what makes their token-parity contracts
    sound."""
    V = is_stop.shape[-1]
    byte_idx = jnp.arange(V, dtype=jnp.int32) // 8
    bit_idx = (jnp.arange(V, dtype=jnp.int32) % 8).astype(jnp.uint8)
    rows = g_bits[g_ids, states]                   # (B, ceil(V/8))
    allowed = (rows[:, byte_idx] >> bit_idx) & jnp.uint8(1)
    return (allowed > 0) | (g_eos_ok[g_ids, states][:, None] & is_stop)


def constrained_logits(logits: jax.Array, prompt_counts: jax.Array,
                       counts: jax.Array, rep: jax.Array,
                       freq: jax.Array, pres: jax.Array,
                       g_bits: jax.Array, g_eos_ok: jax.Array,
                       g_ids: jax.Array, states: jax.Array,
                       is_stop: jax.Array) -> jax.Array:
    """The full constrained head minus sampling: penalties, then the
    DFA mask (order matters only in that masked entries must stay
    masked — penalties never raise a -1e30)."""
    logits = apply_penalties(logits, prompt_counts, counts, rep, freq,
                             pres)
    allow = guided_allow(g_bits, g_eos_ok, g_ids, states, is_stop)
    return jnp.where(allow, logits, _NEG_INF)


def chosen_logprob(logits: jax.Array, sampled: jax.Array) -> jax.Array:
    """(B,) log-probability of each row's sampled token (traceable) —
    the ONE definition both prefill sampling and the fused decode loop
    use, so their logprob semantics can never diverge."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, sampled[:, None], axis=-1)[:, 0]


def stable_topk_logprobs(logp: jax.Array, k: int) -> tuple[jax.Array,
                                                           jax.Array]:
    """((..., k) ids f32, (..., k) logprobs) with an index-stable
    tie-break: the selection key is logp quantized to bf16, which
    collapses sub-bf16 numeric noise (the spread two separately-compiled
    bursts can legitimately disagree by) into EXACT ties, and XLA's
    top_k breaks exact ties by lowest index. So two near-tied
    ALTERNATIVES can never swap order across compilations, while the
    reported logprobs stay the exact f32 values."""
    key = logp.astype(jnp.bfloat16).astype(jnp.float32)
    _, ids = jax.lax.top_k(key, k)
    vals = jnp.take_along_axis(logp, ids, axis=-1)
    return ids.astype(jnp.float32), vals


def topk_logprobs(logits: jax.Array, k: int) -> tuple[jax.Array,
                                                      jax.Array]:
    """((B, k) ids f32, (B, k) logprobs) of the k most likely tokens —
    same log_softmax semantics as chosen_logprob (pre-sampling-filter
    logits, matching OpenAI's 'model distribution' contract). Exact-f32
    ordering: the OpenAI response promises values sorted descending, so
    this path must NOT quantize its selection key (see
    stable_topk_logprobs for the spec lane's index-stable variant)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    vals, ids = jax.lax.top_k(logp, k)
    return ids.astype(jnp.float32), vals


def _sample_tokens_lp_traced(logits, seeds, steps, temperature, top_p,
                             top_k, min_p=None, rows=None,
                             topk_lp: int = 0):
    """sample_tokens + chosen-token logprob (+ optional top-k
    alternatives), PACKED (2 + 2*topk_lp, B) f32 (token ids exact in
    f32; one host transfer instead of two). Rows: [sampled, chosen_lp, topk ids...,
    topk lps...]. `rows` (B,) i32, when given, picks the B rows to
    sample out of a taller `logits` inside the program (a prefill
    round's (Bp, V) output as it left the round: no slice or stack
    launched ahead of the sampler)."""
    if rows is not None:
        logits = logits[rows]
    sampled = sample_tokens_traced(logits, seeds, steps, temperature,
                                   top_p, top_k, min_p)
    packed = [sampled.astype(jnp.float32), chosen_logprob(logits, sampled)]
    if topk_lp:
        ids, vals = topk_logprobs(logits, topk_lp)
        packed += [ids[:, i] for i in range(topk_lp)]
        packed += [vals[:, i] for i in range(topk_lp)]
    return jnp.stack(packed)


sample_tokens_lp = jax.jit(_sample_tokens_lp_traced,
                           static_argnames=("topk_lp",))
