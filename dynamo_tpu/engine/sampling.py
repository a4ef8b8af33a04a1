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

What a batch pays (one TPU v5e, alone on the chip: PR 44 for the candidate
set, PR 49 for the greedy tail; PERF.md §6). The top-k is linear in rows x
vocabulary and is NOT cheap at a wide batch of a large vocabulary: 3.50 ms
of a sampler's 3.82 at (128, 131072). So the candidate set is built under
ONE `lax.cond` on "does any lane of this batch draw", in
`sample_with_logprob`, the function every sampling site takes its token and
its log-probability from (the fused decode loops, the block burst, the
mixed, ragged, guided and pipeline steps, the first-token sampler); none
keeps a condition, an argmax or a log-softmax of its own. A batch in which
one lane draws runs the whole of it for every lane as before (3.77 ms): the
same operations in the same order, so on one compiled program its tokens
are what they were (on the chip a drawn stream still moves whenever XLA
compiles the program around the sampler anew: PERF.md §6, PR 44).

An all-greedy batch wants an argmax and the chosen token's log-probability:
three reductions over one (rows, vocabulary) array (the maximum, where it
first stands, the sum of exponentials), which XLA ran as three passes at
300-420 GB/s each over logits the head had widened to float32 for them.
The greedy branch is now the kernel `greedy_tail` (engine/greedy_tail.py),
which reads the logits ONCE, in the dtype the head made them: the heads of
the fused loops hand on their product as it is (bf16 where the model is),
the condition's operand is that array, and whoever computes on it widens.
Device ms a call, alone on the chip, the XLA form as it stood -> the kernel:
SDAR's (256, 151936) bf16 0.54 -> 0.11 (f32: 0.89 -> 0.21), Nemotron's
(128, 131072) 0.15 -> 0.05 (f32: 0.30 -> 0.09), LFM2's (64, 65536) 0.040
-> 0.015, Mistral's (32, 32768) 0.013 -> 0.006, Qwen's (8, 152064) 0.014
-> 0.008; in the SDAR block burst, where the parent also copied its
position-major logits to float32 and flattened them for the reductions,
1.7 ms a head forward -> 0.09.
Off the TPU, under a mesh (a Mosaic call is not partitioned) and for shapes
the kernel does not tile, the logits are widened ahead of the condition as
they always were and the same two results come from XLA in two passes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.greedy_tail import greedy_tail, greedy_tail_supported

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


def _draw_tokens(logits: jax.Array, seeds: jax.Array, steps: jax.Array,
                 temperature: jax.Array, top_p: jax.Array, top_k: jax.Array,
                 min_p: Optional[jax.Array] = None) -> jax.Array:
    """(B,) i32 tokens of a batch in which some lane draws: the candidate
    set, keys from (seed, step), a categorical draw a lane; a greedy lane
    of such a batch takes its argmax."""
    masked, cand_idx, t = _candidate_mask(logits, temperature, top_p,
                                          top_k, min_p)

    def sample_one(seed, step, lg, tt):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.categorical(key, lg / tt)

    choice = jax.vmap(sample_one)(
        seeds.astype(jnp.uint32), steps.astype(jnp.uint32), masked, t)
    sampled = jnp.take_along_axis(cand_idx, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temperature > 0, sampled,
                     jnp.argmax(logits, axis=-1).astype(jnp.int32))


def sample_tokens_traced(logits: jax.Array, seeds: jax.Array,
                         steps: jax.Array, temperature: jax.Array,
                         top_p: jax.Array, top_k: jax.Array,
                         min_p: Optional[jax.Array] = None) -> jax.Array:
    """logits: (B, V) fp32; seeds/steps: (B,) u32/i32; temperature/top_p:
    (B,) f32; top_k: (B,) i32 (0 = disabled); min_p: (B,) f32 (0 =
    disabled) — drops candidates whose probability is below
    min_p × max-probability (after temperature). temperature <= 0 ⇒
    greedy. Returns (B,) i32 tokens. Traceable. The tokens alone: a site
    that also wants their log-probabilities calls `sample_with_logprob`."""
    return lax.cond(
        jnp.any(temperature > 0),
        lambda: _draw_tokens(logits, seeds, steps, temperature, top_p,
                             top_k, min_p),
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))


def _greedy_tail_runs(logits: jax.Array) -> bool:
    """Whether a greedy batch of these logits goes through the kernel: on
    the TPU, one device's whole rows (a Mosaic call is not partitioned
    over a mesh), a shape it tiles."""
    from dynamo_tpu.engine.attention import use_pallas

    mesh = jax.sharding.get_abstract_mesh()
    return (use_pallas() and (mesh.empty or mesh.size == 1)
            and greedy_tail_supported(logits.shape, logits.dtype))


def sample_with_logprob(logits: jax.Array, seeds: jax.Array,
                        steps: jax.Array, temperature: jax.Array,
                        top_p: jax.Array, top_k: jax.Array,
                        min_p: Optional[jax.Array] = None, *,
                        rows: Optional[jax.Array] = None,
                        best: bool = False) -> tuple:
    """`sample_tokens_traced`'s tokens AND `chosen_logprob` of them:
    (tokens (B,) i32, logprob (B,) f32), the pair every sampling site
    makes. logits (B, V) as the head made them, bf16 or f32.

    One condition, "does any lane of this batch draw". It does: the
    candidate set and the draw as they stood, then `chosen_logprob`. It
    does not: each row's argmax and its log-softmax, which is
    -log(sum(exp(x - max))) because the chosen token IS the maximum. On
    the TPU that is one kernel that reads the logits once, in the dtype
    they come in (`greedy_tail`): the condition's operand is the head's own
    product, widened by nobody (a head that writes f32 for the sampler's
    sake writes twice the bytes: +1.8% TPOT in the SDAR cell, PR 44).
    Elsewhere (off the TPU, under a mesh, a shape the kernel does not
    tile) the logits are widened ahead of the condition, where XLA fuses
    the widening into the head as it always did, and the greedy branch is
    the same two results in two passes, with no row maximum of its own
    and no (B, V) result. The argmax stays inside each branch.

    rows (B,) i32, when given, are the rows of a taller or shorter
    `logits` the B lanes read (a prefill round's output as it left the
    round): the greedy branch reduces the rows there are and gathers two
    numbers a lane, the drawing one gathers the logits. best=True adds a
    third result, the log-probability of each row's most likely token (a
    block-diffusion step's confidence): the greedy branch's second
    result again, max(log_softmax) in the drawing one."""
    kernel = _greedy_tail_runs(logits)
    if not kernel:
        logits = logits.astype(jnp.float32)

    def greedy():
        if kernel:
            tok, lp = greedy_tail(logits)
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            top = jnp.take_along_axis(logits, tok[:, None], axis=-1)
            lp = -jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
        if rows is not None:
            tok, lp = tok[rows], lp[rows]
        return (tok, lp, lp) if best else (tok, lp)

    def draw():
        x = (logits if rows is None else logits[rows]).astype(jnp.float32)
        tok = _draw_tokens(x, seeds, steps, temperature, top_p, top_k,
                           min_p)
        lp = chosen_logprob(x, tok)
        if best:
            return tok, lp, jnp.max(jax.nn.log_softmax(x, axis=-1), axis=-1)
        return tok, lp

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
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
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
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
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
    launched ahead of the sampler; a greedy batch reduces the Bp rows and
    gathers the lanes' two numbers, `sample_with_logprob`)."""
    sampled, chosen = sample_with_logprob(logits, seeds, steps, temperature,
                                          top_p, top_k, min_p, rows=rows)
    packed = [sampled.astype(jnp.float32), chosen]
    if topk_lp:
        ids, vals = topk_logprobs(
            logits if rows is None else logits[rows], topk_lp)
        packed += [ids[:, i] for i in range(topk_lp)]
        packed += [vals[:, i] for i in range(topk_lp)]
    return jnp.stack(packed)


sample_tokens_lp = jax.jit(_sample_tokens_lp_traced,
                           static_argnames=("topk_lp",))
