"""Pipeline-parallel (GPipe-style) prefill over a "pp" mesh axis.

Reference parity: the reference surfaces `--pipeline-parallel-size`
through its TRT-LLM path (`trtllm_utils.py:39,167-170`) and delegates the
actual pipelining to the engine; here the engine is ours. TPU-first
shape: the L layer stack is sharded over "pp" (each stage holds L/S
contiguous layers — an equal slice of the weight bytes, which is what PP
buys: models whose weights don't fit one chip's HBM even under TP).
Microbatches flow stage-to-stage via `lax.ppermute` one neighbor hop per
step (ICI), with the classic GPipe schedule: S + M - 1 steps, stage s
active on microbatch m at step s + m.

Decode is pipelined too (`pp_decode_multi_step`): microbatches of
lanes round-robin through the stages, each stage holding its layer
slice's paged KV, with the sampled token fed back to stage 0 through a
psum mailbox. PP still adds a per-token bubble TP over ICI does not —
on TPU pods TP (and SP for long context) remains the preferred serving
layout — but models whose weights exceed a TP slice's HBM can now
serve BOTH phases under pp (requires n_micro >= n_stages to hide the
feedback latency).

All control flow is a `lax.scan` over the schedule with static shapes —
nothing recompiles per microbatch count change except the schedule
length itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import pcast
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.quant import qm
from dynamo_tpu.models.llama import (
    LlamaConfig,
    _decode_kv,
    _layer_params,
    _write_kv,
    block_out,
    block_qkv,
    dense_layer,
    rms_norm,
)


def _stage_layers(params_local: dict, x: jax.Array, positions: jax.Array,
                  cfg: LlamaConfig) -> jax.Array:
    """Run this stage's layer slice over activations x (B, T, E)."""
    T = x.shape[1]
    mask = jnp.tril(jnp.ones((T, T), bool))

    def one_layer(x, lp):
        return dense_layer(x, lp, positions, mask, cfg), None

    x, _ = lax.scan(one_layer, x, params_local)
    return x


def _pp_forward_local(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                      axis: str, n_stages: int, n_micro: int):
    """Per-stage body (inside shard_map over ``axis``).

    params: layers sharded over L ("pp" slice local); embed/lm_head/norm
    replicated. tokens: (M, Bm, T) microbatches, replicated. Returns
    (M, Bm, V) last-token logits — real only on the last stage."""
    stage = lax.axis_index(axis)
    M, Bm, T = tokens.shape
    E = cfg.hidden_size
    V = cfg.vocab_size
    positions = jnp.arange(T)[None, :]
    layers_local = params["layers"]

    # forward-only neighbor ring: stage s sends to s+1 (no wraparound edge;
    # the permute drops the last stage's send and zero-fills stage 0's recv)
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    out0 = jnp.zeros((M, Bm, V), jnp.float32)
    x0 = jnp.zeros((Bm, T, E), cfg.dtype)
    out0, x0 = pcast((out0, x0), (axis,), to='varying')

    def step(carry, t):
        x_recv, out = carry
        m = t - stage                       # this stage's microbatch index
        active = (m >= 0) & (m < M)
        m_safe = jnp.clip(m, 0, M - 1)
        toks_m = lax.dynamic_index_in_dim(tokens, m_safe, 0,
                                          keepdims=False)   # (Bm, T)
        x_in = jnp.where(stage == 0, params["embed"][toks_m], x_recv)
        y = _stage_layers(layers_local, x_in, positions, cfg)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage: project the microbatch's final token to logits
        xf = rms_norm(y[:, -1], params["final_norm"], cfg.rms_eps)
        logits = qm(xf, params["lm_head"]).astype(jnp.float32)  # (Bm, V)
        write = active & (stage == n_stages - 1)
        out = lax.dynamic_update_index_in_dim(
            out,
            jnp.where(write, logits,
                      lax.dynamic_index_in_dim(out, m_safe, 0, False)),
            m_safe, 0)
        x_next = lax.ppermute(y, axis, perm)
        return (x_next, out), None

    (_, out), _ = lax.scan(step, (x0, out0),
                           jnp.arange(n_stages + n_micro - 1))
    return out[None]  # (1, M, Bm, V) → stacked over pp by out_specs


def pp_specs_for(params: dict) -> dict:
    """pp_param_specs matching THIS param tree (bias/MoE rows only when
    the family has them) — the one probe site, mirroring
    sharding.specs_for."""
    return pp_param_specs("bq" in params["layers"],
                          moe="router" in params["layers"])


def pp_param_specs(with_bias: bool = False, moe: bool = False) -> dict:
    """Layer stacks sharded over "pp" (stage slices); the rest replicated.
    `with_bias` (Qwen2 family) adds the bq/bk/bv stacks; `moe`
    (Mixtral family) swaps the dense FFN rows for the router + the
    (L, X, ...) expert stacks — each stage then holds its layer
    slice's EXPERTS too, which is the pp×moe layout."""
    rows = [("attn_norm", 1), ("wq", 2), ("wk", 2), ("wv", 2), ("wo", 2),
            ("mlp_norm", 1)]
    if moe:
        rows += [("router", 2), ("w_gate", 3), ("w_up", 3),
                 ("w_down", 3)]
    else:
        rows += [("w_gate", 2), ("w_up", 2), ("w_down", 2)]
    if with_bias:
        rows += [("bq", 1), ("bk", 1), ("bv", 1)]
    layer = {k: P("pp", *([None] * n)) for k, n in rows}
    return {"embed": P(None, None), "layers": layer,
            "final_norm": P(None), "lm_head": P(None, None)}


@functools.partial(jax.jit,
                   static_argnames=("cfg", "mesh", "axis", "n_micro"))
def _pp_prefill_jit(params, tokens, cfg: LlamaConfig, mesh: Mesh,
                    axis: str, n_micro: int):
    n_stages = mesh.shape[axis]
    fn = shard_map(
        functools.partial(_pp_forward_local, cfg=cfg, axis=axis,
                          n_stages=n_stages, n_micro=n_micro),
        mesh=mesh,
        in_specs=(pp_specs_for(params), P(None, None, None)),
        out_specs=P(axis, None, None, None))
    return fn(params, tokens)


def pp_prefill_logits(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                      mesh: Mesh, n_micro: int = 2, axis: str = "pp"):
    """Pipeline-parallel forward: tokens (B, T), B divisible by n_micro,
    cfg.num_layers divisible by the "pp" axis size. Returns last-token
    logits (B, V) float32."""
    n_stages = mesh.shape[axis]
    assert cfg.num_layers % n_stages == 0, (
        f"{cfg.num_layers} layers not divisible by pp={n_stages}")
    B, T = tokens.shape
    assert B % n_micro == 0, f"batch {B} not divisible by M={n_micro}"
    mb = tokens.reshape(n_micro, B // n_micro, T)
    sharded_params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, pp_specs_for(params),
        is_leaf=lambda x: not isinstance(x, dict))
    out = _pp_prefill_jit(sharded_params, mb, cfg, mesh, axis, n_micro)
    return out[-1].reshape(B, cfg.vocab_size)


# ---------------------------------------------------------------------------
# paged prefill pipeline (the serving path: writes paged KV per stage)
# ---------------------------------------------------------------------------


def _pp_prefill_paged_local(params, kc_all, vc_all, tokens_c,
                            page_tables, cached_lens, seq_lens,
                            cfg: LlamaConfig, axis: str, n_stages: int,
                            n_chunks: int):
    """Per-stage body: chunk-microbatched paged prefill.

    Microbatches are CHUNKS of the same sequence batch in time order —
    the GPipe schedule delivers chunk c to stage s one step before
    chunk c+1, so every layer's KV for chunk c is written before chunk
    c+1 attends it (same causality the engine's sequential chunk loop
    provides, now pipelined across stages).

    tokens_c: (C, B, Tc); caches (L_local, KVH, N, P, D) stage-local;
    page_tables (B, max_pages); cached_lens/seq_lens (B,). Returns
    ((1, B, V) last-token logits — real on the last stage, kc, vc).
    """
    from dynamo_tpu.engine.attention import paged_attention_prefill

    stage = lax.axis_index(axis)
    C, B, Tc = tokens_c.shape
    E, V, P_ = cfg.hidden_size, cfg.vocab_size, cfg.page_size
    L_local = kc_all.shape[0]

    out0 = jnp.zeros((B, V), jnp.float32)
    x0 = jnp.zeros((B, Tc, E), cfg.dtype)
    out0, x0 = pcast((out0, x0), (axis,), to='varying')
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def step(carry, r):
        x_recv, kc_all, vc_all, out = carry
        c = r - stage
        active = (c >= 0) & (c < C)
        c_safe = jnp.clip(c, 0, C - 1)
        toks = lax.dynamic_index_in_dim(tokens_c, c_safe, 0, False)
        positions = (cached_lens[:, None] + c_safe * Tc
                     + jnp.arange(Tc)[None, :])             # (B, Tc)
        new_valid = (positions < seq_lens[:, None]) & active
        page_ids = jnp.take_along_axis(page_tables, positions // P_,
                                       axis=1)
        offsets = positions % P_

        def flat(a):
            return a.reshape((B * Tc,) + a.shape[2:])

        x = jnp.where(stage == 0, params["embed"][toks], x_recv)
        new_k, new_v = [], []
        for l in range(L_local):
            lp = _layer_params(params, l)
            kc, vc = kc_all[l], vc_all[l]
            q, k, v = block_qkv(x, lp, positions, cfg)
            with jax.named_scope("kv_write"):
                kc, vc = _write_kv(kc, vc, flat(k), flat(v),
                                   flat(page_ids), flat(offsets),
                                   flat(new_valid))
            with jax.named_scope("attn_core"):
                attn = paged_attention_prefill(
                    q, kc, vc, page_tables, positions[:, 0], seq_lens,
                    page_size=P_)                           # (B, Tc, H, D)
            x = block_out(x, attn, lp, cfg)
            new_k.append(kc)
            new_v.append(vc)
        kc_all = jnp.stack(new_k)
        vc_all = jnp.stack(new_v)

        # last stage: lanes whose final new token lives in THIS chunk
        # get their logits written
        xf = rms_norm(x, params["final_norm"], cfg.rms_eps)
        last_rel = seq_lens - 1 - cached_lens - c_safe * Tc  # (B,)
        in_chunk = (last_rel >= 0) & (last_rel < Tc) & active
        idx = jnp.clip(last_rel, 0, Tc - 1)
        x_last = jnp.take_along_axis(xf, idx[:, None, None],
                                     axis=1)[:, 0]           # (B, E)
        logits = qm(x_last, params["lm_head"]).astype(jnp.float32)
        write = in_chunk & (stage == n_stages - 1)
        out = jnp.where(write[:, None], logits, out)
        x_next = lax.ppermute(x, axis, perm)
        return (x_next, kc_all, vc_all, out), None

    (_, kc_all, vc_all, out), _ = lax.scan(
        step, (x0, kc_all, vc_all, out0),
        jnp.arange(n_chunks + n_stages - 1))
    return out[None], kc_all, vc_all


@functools.partial(jax.jit,
                   static_argnames=("cfg", "mesh", "axis", "n_chunks"),
                   donate_argnums=(1, 2))
def _pp_prefill_paged_jit(params, k_cache, v_cache, tokens_c,
                          page_tables, cached_lens, seq_lens,
                          cfg: LlamaConfig, mesh: Mesh, axis: str,
                          n_chunks: int):
    n_stages = mesh.shape[axis]
    fn = shard_map(
        functools.partial(_pp_prefill_paged_local, cfg=cfg, axis=axis,
                          n_stages=n_stages, n_chunks=n_chunks),
        mesh=mesh,
        in_specs=(pp_specs_for(params), pp_cache_specs(), pp_cache_specs(),
                  P(None, None, None), P(None, None), P(None), P(None)),
        out_specs=(P(axis, None, None), pp_cache_specs(),
                   pp_cache_specs()))
    return fn(params, k_cache, v_cache, tokens_c, page_tables,
              cached_lens, seq_lens)


def pp_prefill_paged(params: dict, k_cache, v_cache, tokens: jax.Array,
                     page_tables: jax.Array, cached_lens: jax.Array,
                     seq_lens: jax.Array, cfg: LlamaConfig, mesh: Mesh,
                     chunk: int, axis: str = "pp"):
    """Serving prefill under pp: tokens (B, T) uncached suffixes (padded;
    T a multiple of `chunk`), paged KV written stage-locally, last-token
    logits (B, V) returned. Greedy-equivalent to the engine's sequential
    chunk loop on the same weights (the schedule changes WHERE layers
    run, not what they compute)."""
    n_stages = mesh.shape[axis]
    assert cfg.num_layers % n_stages == 0
    B, T = tokens.shape
    assert T % chunk == 0, (T, chunk)
    C = T // chunk
    tokens_c = jnp.swapaxes(tokens.reshape(B, C, chunk), 0, 1)  # (C,B,Tc)
    out, k_cache, v_cache = _pp_prefill_paged_jit(
        params, k_cache, v_cache, tokens_c, page_tables,
        jnp.asarray(cached_lens), jnp.asarray(seq_lens), cfg, mesh, axis,
        C)
    return out[-1], k_cache, v_cache   # last stage holds the real rows


# ---------------------------------------------------------------------------
# decode pipeline
# ---------------------------------------------------------------------------


def pp_cache_specs() -> P:
    """Paged KV caches stacked (L, KVH, N, P, D), layer axis over pp."""
    return P("pp", None, None, None, None)


def _pp_decode_local(params, k_cache, v_cache, tokens0, positions,
                     page_tables, valid, seeds, steps0, temperature,
                     top_p, top_k, min_p, rep_pen, freq_pen, pres_pen,
                     prompt_counts, out_counts, g_bits, g_next,
                     g_eos_ok, g_ids, g_states, stop_ids,
                     cfg: LlamaConfig, axis: str,
                     n_stages: int, n_micro: int, num_steps: int,
                     use_constrained: bool = False, topk_lp: int = 0):
    """Per-stage body. tokens0/positions/valid/seeds/steps0/temperature/
    top_p/top_k (+ min_p/rep/freq/pres_pen when constrained): (M, Bm);
    page_tables: (M, Bm, max_pages); prompt_counts/out_counts:
    (M, Bm, V); guided tables (g_bits/g_next/g_eos_ok) replicated,
    g_ids/g_states: (M, Bm); stop_ids: (M, Bm, K); caches
    (L_local, KVH, N, P, D) stage-local. Returns
    (2 + 2*topk_lp, num_steps, M, Bm) packed rows (real on the last
    stage) and the updated caches.

    use_constrained: the LAST stage applies the same constrained
    sampling head as decode_multi_step_guided (penalties from a carried
    per-microbatch counts histogram, DFA mask, min_p) — every stage
    executes the same code on its (garbage) logits, but only the last
    stage's chain is real: its sampled tokens gate the out/mailbox/
    state/count updates through `write`, so the other stages' carried
    copies never update and never matter."""
    from dynamo_tpu.engine.attention import paged_attention_decode
    from dynamo_tpu.engine.sampling import (
        constrained_logits,
        sample_with_logprob,
        stop_token_mask,
        topk_logprobs,
    )

    stage = lax.axis_index(axis)
    M, Bm = tokens0.shape
    E = cfg.hidden_size
    L_local = k_cache.shape[0]
    total = num_steps * n_micro
    n_rows = 2 + 2 * topk_lp

    out0 = jnp.zeros((n_rows, num_steps, M, Bm), jnp.float32)
    x0 = jnp.zeros((Bm, E), cfg.dtype)
    out0, x0 = pcast((out0, x0), (axis,), to='varying')
    perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]
    if use_constrained:
        V = cfg.vocab_size
        K_stop = stop_ids.shape[-1]
        # (M, Bm, V): which vocab entries are the lane's stop tokens
        is_stop = stop_token_mask(
            stop_ids.reshape(M * Bm, K_stop), V).reshape(M, Bm, V)

    def step(carry, r):
        x_recv, mailbox, gst, counts, kc_all, vc_all, out = carry
        p = r - stage
        active = (p >= 0) & (p < total)
        p_safe = jnp.clip(p, 0, total - 1)
        k_idx = p_safe // n_micro
        m = p_safe % n_micro
        tok_m = lax.dynamic_index_in_dim(mailbox, m, 0, False)   # (Bm,)
        pos_m = lax.dynamic_index_in_dim(positions, m, 0,
                                         False) + k_idx
        tbl_m = lax.dynamic_index_in_dim(page_tables, m, 0, False)
        valid_m = lax.dynamic_index_in_dim(valid, m, 0, False) & active

        x_in = jnp.where(stage == 0, params["embed"][tok_m], x_recv)
        page_ids, offsets, lengths = _decode_kv(tbl_m, pos_m, valid_m, cfg)
        x = x_in
        new_k, new_v = [], []
        for l in range(L_local):
            lp = _layer_params(params, l)
            kc, vc = kc_all[l], vc_all[l]
            q, k, v = block_qkv(x, lp, pos_m, cfg)
            with jax.named_scope("kv_write"):
                kc, vc = _write_kv(kc, vc, k, v, page_ids, offsets,
                                   valid_m)
            with jax.named_scope("attn_core"):
                attn = paged_attention_decode(
                    q, kc, vc, lengths, tbl_m, page_size=cfg.page_size)
            x = block_out(x, attn, lp, cfg)
            new_k.append(kc)
            new_v.append(vc)
        kc_all = jnp.stack(new_k)
        vc_all = jnp.stack(new_v)

        xf = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = qm(xf, params["lm_head"])
        write = active & (stage == n_stages - 1)
        minp_m = None
        if use_constrained:
            # the SAME head as decode_multi_step_guided (one shared
            # definition: sampling.constrained_logits), then min_p in
            # the sampler
            st_m = lax.dynamic_index_in_dim(gst, m, 0, False)   # (Bm,)
            cnt_m = lax.dynamic_index_in_dim(counts, m, 0, False)
            gid_m = lax.dynamic_index_in_dim(g_ids, m, 0, False)
            logits = constrained_logits(
                logits.astype(jnp.float32),
                lax.dynamic_index_in_dim(prompt_counts, m, 0, False),
                cnt_m,
                lax.dynamic_index_in_dim(rep_pen, m, 0, False),
                lax.dynamic_index_in_dim(freq_pen, m, 0, False),
                lax.dynamic_index_in_dim(pres_pen, m, 0, False),
                g_bits, g_eos_ok, gid_m, st_m,
                lax.dynamic_index_in_dim(is_stop, m, 0, False))
            minp_m = lax.dynamic_index_in_dim(min_p, m, 0, False)
        sampled, lp_chosen = sample_with_logprob(
            logits,
            lax.dynamic_index_in_dim(seeds, m, 0, False),
            lax.dynamic_index_in_dim(steps0, m, 0, False) + k_idx,
            lax.dynamic_index_in_dim(temperature, m, 0, False),
            lax.dynamic_index_in_dim(top_p, m, 0, False),
            lax.dynamic_index_in_dim(top_k, m, 0, False),
            minp_m)
        if use_constrained:
            new_st = g_next[gid_m, st_m, sampled].astype(jnp.int32)
            gst = lax.dynamic_update_index_in_dim(
                gst, jnp.where(write, new_st, st_m), m, 0)
            new_cnt = cnt_m.at[jnp.arange(Bm), sampled].add(
                (valid_m & write).astype(cnt_m.dtype))
            counts = lax.dynamic_update_index_in_dim(counts, new_cnt,
                                                     m, 0)

        row_list = [sampled.astype(jnp.float32), lp_chosen]
        if topk_lp:
            # alternatives from the same (possibly penalized+masked)
            # logits the lane sampled from — matches the plain engine's
            # constrained-burst semantics
            tk_ids, tk_vals = topk_logprobs(logits, topk_lp)
            row_list += [tk_ids[:, i] for i in range(topk_lp)]
            row_list += [tk_vals[:, i] for i in range(topk_lp)]
        cur = lax.dynamic_slice(out, (0, k_idx, m, 0),
                                (n_rows, 1, 1, Bm))
        upd = jnp.where(write,
                        jnp.stack(row_list)[:, None, None, :],
                        cur)
        out = lax.dynamic_update_slice(out, upd, (0, k_idx, m, 0))
        # feedback: the last stage's sampled token becomes microbatch
        # m's next step-0 input on EVERY stage (psum broadcast — only
        # the last stage contributes a delta)
        delta = jnp.where(write, sampled - tok_m, 0)
        delta_all = lax.psum(
            jnp.zeros((M, Bm), jnp.int32)
            .at[m].set(delta), axis)
        mailbox = mailbox + delta_all
        x_next = lax.ppermute(x, axis, perm_fwd)
        return (x_next, mailbox, gst, counts, kc_all, vc_all, out), None

    mailbox0 = pcast(tokens0, (axis,), to='varying')
    if use_constrained:
        gst0 = pcast(g_states.astype(jnp.int32), (axis,),
                         to='varying')
        counts0 = pcast(out_counts.astype(jnp.int32), (axis,),
                            to='varying')
    else:
        gst0 = pcast(jnp.zeros((M, Bm), jnp.int32), (axis,),
                         to='varying')
        counts0 = pcast(jnp.zeros((M, Bm, 1), jnp.int32), (axis,),
                            to='varying')
    rounds = total + n_stages - 1
    (_, _, _, _, k_cache, v_cache, out), _ = lax.scan(
        step, (x0, mailbox0, gst0, counts0, k_cache, v_cache, out0),
        jnp.arange(rounds))
    return out[None], k_cache, v_cache


@functools.partial(jax.jit,
                   static_argnames=("cfg", "mesh", "axis", "n_micro",
                                    "num_steps", "use_constrained",
                                    "topk_lp"),
                   donate_argnums=(1, 2))
def _pp_decode_jit(params, k_cache, v_cache, tokens, positions,
                   page_tables, valid, seeds, steps0, temperature,
                   top_p, top_k, min_p, rep_pen, freq_pen, pres_pen,
                   prompt_counts, out_counts, g_bits, g_next, g_eos_ok,
                   g_ids, g_states, stop_ids,
                   cfg: LlamaConfig, mesh: Mesh, axis: str,
                   n_micro: int, num_steps: int,
                   use_constrained: bool, topk_lp: int):
    n_stages = mesh.shape[axis]
    mb2 = P(None, None)
    mb3 = P(None, None, None)
    fn = shard_map(
        functools.partial(_pp_decode_local, cfg=cfg, axis=axis,
                          n_stages=n_stages, n_micro=n_micro,
                          num_steps=num_steps,
                          use_constrained=use_constrained,
                          topk_lp=topk_lp),
        mesh=mesh,
        in_specs=(pp_specs_for(params), pp_cache_specs(), pp_cache_specs(),
                  mb2, mb2, mb3,
                  mb2, mb2, mb2,
                  mb2, mb2, mb2,
                  mb2, mb2, mb2, mb2,   # min_p, rep/freq/pres_pen
                  mb3, mb3,             # prompt_counts, out_counts
                  mb3, mb3, mb2,        # g_bits, g_next, g_eos_ok
                  mb2, mb2, mb3),       # g_ids, g_states, stop_ids
        out_specs=(P(axis, None, None, None, None),
                   pp_cache_specs(), pp_cache_specs()))
    return fn(params, k_cache, v_cache, tokens, positions, page_tables,
              valid, seeds, steps0, temperature, top_p, top_k,
              min_p, rep_pen, freq_pen, pres_pen, prompt_counts,
              out_counts, g_bits, g_next, g_eos_ok, g_ids, g_states,
              stop_ids)


def pp_decode_multi_step(params: dict, k_cache, v_cache, tokens,
                         positions, page_tables, valid, seeds, steps0,
                         temperature, top_p, top_k, cfg: LlamaConfig,
                         mesh: Mesh, num_steps: int, n_micro: int = 2,
                         axis: str = "pp",
                         min_p=None, rep_pen=None, freq_pen=None,
                         pres_pen=None, prompt_counts=None,
                         out_counts=None, g_bits=None, g_next=None,
                         g_eos_ok=None, g_ids=None, g_states=None,
                         stop_ids=None, use_constrained: bool = False,
                         topk_lp: int = 0):
    """Microbatched pipeline decode: `num_steps` fused decode+sample
    steps for B lanes split into n_micro groups that round-robin
    through the pp stages (GPipe schedule with a sampled-token feedback
    mailbox). Greedy output is identical to `decode_multi_step` on the
    same weights — the pipeline changes WHERE layers run, not what they
    compute (tests/test_moe_pp.py proves token equality).

    params: host/replicated-layout pytree (placed here with layer
    stacks sharded over "pp"); k_cache/v_cache: (L, KVH, N, P, D)
    stacked paged caches (sharded over "pp" on L); tokens/positions/
    valid/seeds/steps0/temperature/top_p/top_k: (B,);
    page_tables: (B, max_pages). B divisible by n_micro;
    n_micro >= n_stages (the schedule needs a microbatch's step-k
    token sampled before its step-k+1 slot reaches stage 0).

    use_constrained: the full constrained sampling matrix (grammar
    masks via the stacked DFA tables, min_p, OpenAI/HF penalties) runs
    on the last stage — pp engines serve the SAME feature set as the
    plain engine (the reference's engines own sampling uniformly
    regardless of parallelism: trtllm_utils.py:167-176). min_p/
    rep_pen/freq_pen/pres_pen: (B,); prompt_counts/out_counts: (B, V);
    g_ids/g_states: (B,); stop_ids: (B, K). topk_lp appends top-k
    alternative id/logprob rows exactly like decode_multi_step.

    Returns (packed (2 + 2*topk_lp, num_steps, B) f32 —
    decode_multi_step's row layout, k_cache, v_cache)."""
    n_stages = mesh.shape[axis]
    assert cfg.num_layers % n_stages == 0
    assert n_micro >= n_stages, (
        f"n_micro={n_micro} must be >= pp stages {n_stages}")
    B = tokens.shape[0]
    assert B % n_micro == 0, f"batch {B} not divisible by {n_micro}"
    Bm = B // n_micro

    def mb(a):
        return a.reshape(n_micro, Bm, *a.shape[1:])

    if use_constrained:
        cargs = (mb(min_p), mb(rep_pen), mb(freq_pen), mb(pres_pen),
                 mb(prompt_counts), mb(out_counts),
                 jnp.asarray(g_bits), jnp.asarray(g_next),
                 jnp.asarray(g_eos_ok), mb(g_ids), mb(g_states),
                 mb(stop_ids))
    else:
        z2 = jnp.zeros((n_micro, Bm), jnp.float32)
        z2i = jnp.zeros((n_micro, Bm), jnp.int32)
        z3 = jnp.zeros((n_micro, Bm, 1), jnp.int32)
        cargs = (z2, z2, z2, z2, z3, z3,
                 jnp.zeros((1, 1, 1), jnp.uint8),
                 jnp.zeros((1, 1, 1), jnp.int16),
                 jnp.zeros((1, 1), bool), z2i, z2i, z3)

    sharded_params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, pp_specs_for(params),
        is_leaf=lambda x: not isinstance(x, dict))
    cache_ns = NamedSharding(mesh, pp_cache_specs())
    k_cache = jax.device_put(k_cache, cache_ns)
    v_cache = jax.device_put(v_cache, cache_ns)
    out, k_cache, v_cache = _pp_decode_jit(
        sharded_params, k_cache, v_cache, mb(tokens), mb(positions),
        mb(page_tables), mb(valid), mb(seeds), mb(steps0),
        mb(temperature), mb(top_p), mb(top_k), *cargs, cfg, mesh, axis,
        n_micro, num_steps, use_constrained, topk_lp)
    # (S, R, K, M, Bm) stacked over pp → last stage holds the real rows
    packed = out[-1].reshape(2 + 2 * topk_lp, num_steps, B)
    return packed, k_cache, v_cache
