"""Speculative decoding: draft-proposes, target-verifies, fused on device.

Reference parity: the reference exposes speculative decode through its
delegated engines and surfaces `SpecDecodeStats` in worker metrics
(`lib/llm/src/kv_router/protocols.rs` ForwardPassMetrics). We own the
implementation, TPU-first:

- The draft model shares the TARGET's page tables: its paged KV caches are
  allocated with the same (num_pages, page_size) geometry, so one page
  allocation covers both models. The engine never trusts prefix pages to
  hold draft KV (disagg imports, KVBM onboarding and non-spec fallback
  bursts write target KV only): the draft prefills the full prompt and
  replays fallback-decoded tokens (`_draft_catchup`) before a spec burst.
- Rollback is FREE with paged attention: rejected positions leave garbage
  KV in the cache, but attention masks strictly by sequence length, and
  the next accepted tokens overwrite those slots. No copy, no rewind.
- Acceptance runs on device inside a fused `num_iters` loop (one host
  sync per burst, same contract as `decode_multi_step`): per-lane
  Leviathan et al. rejection sampling —
    greedy lanes  (temperature == 0): accept while target argmax == draft
    stochastic lanes: accept draft token c with prob min(1, p_t(c)/p_d(c))
      over the lane's ACTUAL sampling distribution — the temperature-
      scaled softmax restricted by its top-p/top-k/min_p filter and
      penalty-adjusted logits (sampling.filtered_probs +
      apply_penalties; filtering target and draft identically preserves
      Leviathan correctness). Greedy lanes are the one-hot special case
      of the same test (exact argmax equality), so one code path serves
      EVERY sampling config — guided grammars mask both sides through
      the DFA row, penalties ride a tentative-counts chain (see
      spec_decode_multi_step), min_p rides the shared filter.

Output is PACKED into one f32 array (3, num_iters, gamma+1, B):
row 0 token ids, row 1 chosen-token target logprobs, row 2 the per-lane
emitted-count (broadcast) — one host transfer per burst.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.quant import qm
from dynamo_tpu.engine.sampling import stable_topk_logprobs
from dynamo_tpu.models.llama import (
    LlamaConfig,
    _decode_once,
    paged_forward,
    rms_norm,
)

# xor'd into seeds for the draft's sampling stream so draft and target
# never consume the same (seed, step) randomness
_DRAFT_SEED_SALT = jnp.uint32(0x9E3779B9)


def _lane_probs(logits: jax.Array, temperature: jax.Array,
                top_p: jax.Array, top_k: jax.Array,
                min_p=None) -> jax.Array:
    """Per-lane ACTUAL sampling distribution for (B, V) or (B, G, V)
    logits (sampling.filtered_probs, vectorized over the middle dim)."""
    from dynamo_tpu.engine.sampling import filtered_probs

    if logits.ndim == 2:
        return filtered_probs(logits, temperature, top_p, top_k, min_p)
    b, g, v = logits.shape
    flat = filtered_probs(
        logits.reshape(b * g, v),
        jnp.repeat(temperature, g), jnp.repeat(top_p, g),
        jnp.repeat(top_k, g),
        None if min_p is None else jnp.repeat(min_p, g))
    return flat.reshape(b, g, v)


def _categorical(key: jax.Array, probs: jax.Array) -> jax.Array:
    """Sample index from a probability vector (log trick; probs >= 0)."""
    return jax.random.categorical(key, jnp.log(jnp.maximum(probs, 1e-30)))


@partial(jax.jit,
         static_argnames=("cfg", "draft_cfg", "gamma", "num_iters",
                          "use_guided", "topk_lp", "use_penalties"),
         donate_argnums=(2, 3, 4, 5))
def spec_decode_multi_step(
        params: dict, draft_params: dict,
        k_cache: tuple, v_cache: tuple,
        dk_cache: tuple, dv_cache: tuple,
        tokens: jax.Array, positions: jax.Array, page_tables: jax.Array,
        valid: jax.Array, seeds: jax.Array, steps0: jax.Array,
        temperature: jax.Array, top_p: jax.Array, top_k: jax.Array,
        cfg: LlamaConfig, draft_cfg: LlamaConfig,
        gamma: int, num_iters: int,
        use_guided: bool = False,
        g_bits=None, g_next=None, g_eos_ok=None,
        g_ids=None, g_states=None, stop_ids=None,
        topk_lp: int = 0,
        min_p=None,
        use_penalties: bool = False,
        rep_pen=None, freq_pen=None, pres_pen=None,
        prompt_counts=None, out_counts=None):
    """`num_iters` fused draft→verify→accept iterations, ONE host sync.

    tokens/positions/valid/seeds/steps0/temperature: (B,). Pages for
    positions .. positions + num_iters*(gamma+1) - 1 must be
    pre-allocated in `page_tables` (engine guarantees).

    use_guided: grammar-constrained lanes ride the spec burst — draft
    proposals AND target verification distributions are masked by each
    lane's DFA row (llm/guided.py tables; slot 0 = trivial grammar for
    unguided lanes). The Leviathan test stays correct because draft and
    target share the identical masked support, and the DFA state at
    every verified position equals the draft's tentative state on the
    accepted prefix (accepted tokens ARE the draft's proposals). Lane
    stop tokens become legal where the grammar accepts (g_eos_ok), same
    overlay as decode_multi_step_guided.

    Returns (packed (3 + 2*topk_lp, num_iters, gamma+1, B) f32,
    k_cache, v_cache, dk_cache, dv_cache, new_positions (B,)); packed
    rows: token ids / target logprobs / emitted-count per (iter, lane)
    (count broadcast along the gamma+1 axis; slots >= count are
    padding). topk_lp > 0 appends top-k alternative ids then their
    logprobs (same log_softmax as the chosen row — the target verify
    forward's distribution, so spec and plain bursts report identical
    alternatives under greedy).

    min_p: optional (B,) — threaded into filtered_probs on BOTH the
    draft and target sides, so min_p lanes ride spec bursts with the
    Leviathan test intact (identical filtered support both sides).

    use_penalties: OpenAI/HF sampling penalties ride the burst too.
    rep/freq/pres_pen: (B,); prompt_counts/out_counts: (B, V) token
    histograms at burst start. The draft chain carries TENTATIVE output
    counts (each proposal increments its token), and target
    verification at position i penalizes with the counts after the
    first i proposals — identical to what the draft used when sampling
    proposal i+1, because the accepted prefix IS the proposal prefix
    (the same argument that makes the guided DFA-state chain sound).
    After acceptance the real counts resume from the accepted prefix's
    entry plus the extra token. One apply_penalties definition
    (engine/sampling.py) serves both sides, so spec and constrained
    bursts can never diverge on penalty semantics.
    """
    B = tokens.shape[0]
    G1 = gamma + 1
    draft_seeds = seeds.astype(jnp.uint32) ^ _DRAFT_SEED_SALT
    if use_guided:
        from dynamo_tpu.engine.sampling import (
            guided_allow,
            stop_token_mask,
        )

        is_stop = stop_token_mask(stop_ids, cfg.vocab_size)   # (B, V)

        def allow_rows(states):
            return guided_allow(g_bits, g_eos_ok, g_ids, states, is_stop)

        def advance(states, toks_):
            return g_next[g_ids, states, toks_].astype(jnp.int32)
    else:
        def allow_rows(states):
            return None

        def advance(states, toks_):
            return states

    def mask(logits, allow):
        if allow is None:
            return logits
        return jnp.where(allow, logits, -1e30)

    if use_penalties:
        from dynamo_tpu.engine.sampling import apply_penalties

        def pen(logits, counts):
            return apply_penalties(logits, prompt_counts, counts,
                                   rep_pen, freq_pen, pres_pen)

        def bump(counts, toks_):
            return counts.at[jnp.arange(B), toks_].add(
                valid.astype(counts.dtype))
    else:
        def pen(logits, counts):
            return logits

        def bump(counts, toks_):
            return counts

    def one_iter(it, carry):
        cur, pos, kc, vc, dk, dv, steps, gst, oc, out = carry

        # -- draft: gamma autoregressive proposals (its own small cache).
        # gamma+1 forwards: the last one's logits are unused but it WRITES
        # d_gamma's KV, so after an all-accept iteration the draft cache
        # has no hole at pos+gamma (a stale slot there would poison every
        # later draft attention over it).
        d_tokens = [cur]
        d_probs = []
        d_allows = []        # per-position grammar masks (guided only)
        d_states = [gst]     # DFA state BEFORE sampling position j+1
        d_counts = [oc]      # tentative counts BEFORE position j+1
        dtok = cur
        st = gst
        ct = oc
        for j in range(gamma + 1):
            dlogits, dk, dv = _decode_once(
                draft_params, dk, dv, dtok, pos + j, page_tables, valid,
                draft_cfg)
            dlogits = dlogits.astype(jnp.float32)
            if j == gamma:
                break
            allow_j = allow_rows(st)
            dp = _lane_probs(mask(pen(dlogits, ct), allow_j),
                             temperature, top_p, top_k, min_p)
            key = jax.vmap(
                lambda s, st_: jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(s), st_),
                    jnp.uint32(j))
            )(draft_seeds, steps)
            stoch = jax.vmap(_categorical)(key, dp)
            dtok = jnp.where(temperature > 0, stoch,
                             jnp.argmax(dp, axis=-1)).astype(jnp.int32)
            d_tokens.append(dtok)
            d_probs.append(dp)
            d_allows.append(allow_j)
            st = advance(st, dtok)
            d_states.append(st)
            ct = bump(ct, dtok)
            d_counts.append(ct)
        verify_toks = jnp.stack(d_tokens, axis=1)          # (B, G1)
        draft_p = jnp.stack(d_probs, axis=1)               # (B, gamma, V)

        # -- target: one forward over all G1 positions ---------------------
        seq_lens = jnp.where(valid, pos + G1, pos)
        x, kc, vc = paged_forward(params, kc, vc, verify_toks, page_tables,
                                  pos, seq_lens, cfg, False)
        logits = qm(x, params["lm_head"]).astype(jnp.float32)  # (B, G1, V)
        if use_penalties:
            # position i's counts = counts after the first i proposals —
            # exactly what the draft used there (accepted prefix ==
            # proposal prefix). One flat apply_penalties call keeps THE
            # definition shared with the constrained burst.
            from dynamo_tpu.engine.sampling import apply_penalties

            counts_stack = jnp.stack(d_counts, axis=1)     # (B, G1, V)
            V = logits.shape[-1]
            logits = apply_penalties(
                logits.reshape(B * G1, V),
                jnp.repeat(prompt_counts, G1, axis=0),
                counts_stack.reshape(B * G1, V),
                jnp.repeat(rep_pen, G1), jnp.repeat(freq_pen, G1),
                jnp.repeat(pres_pen, G1)).reshape(B, G1, V)
        if use_guided:
            # mask position i by the state reached after the accepted
            # prefix — identical to the draft's tentative state there
            allow_all = jnp.stack(
                d_allows + [allow_rows(d_states[gamma])],
                axis=1)                                    # (B, G1, V)
            logits = jnp.where(allow_all, logits, -1e30)
        target_p = _lane_probs(logits, temperature, top_p, top_k, min_p)

        # -- acceptance ----------------------------------------------------
        cand = verify_toks[:, 1:]                          # (B, gamma)
        p_t = jnp.take_along_axis(
            target_p[:, :gamma], cand[..., None], axis=-1)[..., 0]
        p_d = jnp.take_along_axis(draft_p, cand[..., None], axis=-1)[..., 0]
        ukey = jax.vmap(
            lambda s, st: jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(s), st),
                jnp.uint32(0x5EC0))
        )(seeds.astype(jnp.uint32), steps)
        u = jax.vmap(lambda k: jax.random.uniform(k, (gamma,)))(ukey)
        # one test for every lane: greedy dists are one-hots, so the
        # ratio test degenerates to exact argmax equality there
        ok = u * jnp.maximum(p_d, 1e-30) < p_t             # (B, gamma)
        n_acc = jnp.argmin(
            jnp.concatenate([ok, jnp.zeros((B, 1), bool)], axis=1)
            .astype(jnp.int32), axis=1)                    # leading trues

        # -- extra token: residual sample (reject) or bonus (all accept) ---
        pt_at_n = jnp.take_along_axis(
            target_p, n_acc[:, None, None], axis=1)[:, 0]
        pd_at_n = jnp.take_along_axis(
            jnp.concatenate(
                [draft_p, jnp.zeros((B, 1, draft_p.shape[-1]),
                                    jnp.float32)], axis=1),
            n_acc[:, None, None], axis=1)[:, 0]
        residual = jnp.maximum(pt_at_n - pd_at_n, 0.0)
        res_mass = residual.sum(axis=-1, keepdims=True)
        # degenerate residual (p_t == p_d exactly) → fall back to p_t
        res_dist = jnp.where(res_mass > 1e-9, residual / res_mass, pt_at_n)
        dist = jnp.where((n_acc == gamma)[:, None], pt_at_n, res_dist)
        xkey = jax.vmap(
            lambda s, st: jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(s), st),
                jnp.uint32(0xB0E5))
        )(seeds.astype(jnp.uint32), steps + n_acc)
        stoch_x = jax.vmap(_categorical)(xkey, dist)
        extra = jnp.where(temperature > 0, stoch_x,
                          jnp.argmax(dist, axis=-1)).astype(jnp.int32)

        # -- emit ----------------------------------------------------------
        emitted = jnp.where(
            jnp.arange(gamma)[None, :] < n_acc[:, None], cand, 0)
        emitted = jnp.concatenate([emitted, jnp.zeros((B, 1), jnp.int32)],
                                  axis=1)                  # (B, G1)
        emitted = emitted.at[jnp.arange(B), n_acc].set(extra)
        count = n_acc + 1                                  # (B,)
        logp_all = jax.nn.log_softmax(logits, axis=-1)
        chosen_lp = jnp.take_along_axis(
            logp_all, emitted[..., None], axis=-1)[..., 0]  # (B, G1)

        out = out.at[0, it].set(emitted.T.astype(jnp.float32))
        out = out.at[1, it].set(chosen_lp.T)
        out = out.at[2, it].set(
            jnp.broadcast_to(count[None, :].astype(jnp.float32), (G1, B)))
        if topk_lp:
            # top-k alternatives of every verified position, from the
            # same (possibly DFA-masked) target distribution the chosen
            # logprob uses; the engine slices the emitted prefix. Two
            # row-block writes, not 2*k scatters (trace size matters in
            # this already-large fused kernel). stable_topk_logprobs
            # keeps near-tie ordering identical across separately
            # compiled bursts.
            tk_ids, tk_vals = stable_topk_logprobs(logp_all, topk_lp)
            out = lax.dynamic_update_slice(
                out, jnp.transpose(tk_ids, (2, 1, 0))[:, None],
                (3, it, 0, 0))
            out = lax.dynamic_update_slice(
                out, jnp.transpose(tk_vals, (2, 1, 0))[:, None],
                (3 + topk_lp, it, 0, 0))

        last = emitted[jnp.arange(B), n_acc]
        new_pos = jnp.where(valid, pos + count, pos)
        if use_guided:
            # state after the accepted prefix, advanced by the extra
            # token (d_states[i] = state before sampling position i+1)
            states_stack = jnp.stack(d_states, axis=1)     # (B, G1)
            st_at_n = jnp.take_along_axis(
                states_stack, n_acc[:, None], axis=1)[:, 0]
            new_gst = advance(st_at_n, last)
        else:
            new_gst = gst
        if use_penalties:
            # counts resume from the accepted prefix's tentative entry
            # (rejected proposals never happened) plus the extra token
            oc_at_n = jnp.take_along_axis(
                counts_stack, n_acc[:, None, None], axis=1)[:, 0]
            new_oc = bump(oc_at_n, last)
        else:
            new_oc = oc
        return (last, new_pos, kc, vc, dk, dv,
                steps + count.astype(jnp.uint32), new_gst, new_oc, out)

    out0 = jnp.zeros((3 + 2 * topk_lp, num_iters, G1, B),
                     dtype=jnp.float32)
    gst0 = (g_states.astype(jnp.int32) if use_guided
            else jnp.zeros((B,), jnp.int32))
    oc0 = (out_counts.astype(jnp.int32) if use_penalties
           else jnp.zeros((), jnp.int32))
    (cur, pos, k_cache, v_cache, dk_cache, dv_cache, _, _, _,
     out) = lax.fori_loop(
        0, num_iters, one_iter,
        (tokens, positions, k_cache, v_cache, dk_cache, dv_cache,
         steps0.astype(jnp.uint32), gst0, oc0, out0))
    return out, k_cache, v_cache, dk_cache, dv_cache, pos
