"""Llama-family model: pure-JAX, paged-KV, TPU-first.

Replaces the reference's engine delegation (vLLM et al., SURVEY.md §2.7)
with an owned implementation. Design for XLA/TPU:
- static shapes everywhere: prefill length and decode batch are bucketed by
  the scheduler; padding is masked
- KV cache is paged: per layer, K and V of shape
  ``(num_kv_heads, num_pages, page_size, head_dim)`` — the layout the TPU
  pallas paged-attention kernel wants. Caches are a **tuple of per-layer
  arrays, and the layer loop is unrolled** (params stay L-stacked; each
  layer statically slices its weights). Measured on v5e: any layout that
  routes the caches through `lax.scan` xs/ys or slices a stacked
  (L, ...) cache per layer makes XLA materialize a full cache copy per
  layer — decode time then scales with *total* cache size (25.6 ms/step
  at 2048 pages on a 1.1B model). Per-layer arrays + the aliased pallas
  kv-write keep every update truly in place: 10.7 ms/step, independent
  of cache size.
- **page 0 is a scratch page**: padding lanes scatter their KV there, so
  real allocations start at page 1 (engine/pages.py enforces this)
- bfloat16 params/activations; fp32 for norm/softmax/logits
- tensor parallelism via `jax.sharding`: heads/ffn sharded on the "tp" mesh
  axis, XLA inserts the collectives (see engine/sharding.py)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.attention import (paged_attention_decode,
                                         paged_attention_prefill)
from dynamo_tpu.engine.pages import kv_layer_shape
from dynamo_tpu.engine.quant import qm


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Qwen2-family attention: q/k/v projections carry additive biases
    # (config.json "Qwen2ForCausalLM"; llama/mistral set no bias). The
    # layer dict gains bq/bk/bv leaves and every forward adds them via
    # qkv_proj — one switch covers paged, dense, sp, and pp paths.
    attention_bias: bool = False
    # Qwen3-family attention: RMS norm over each head's D of q and k,
    # one weight vector a layer each (q_norm / k_norm leaves), before
    # RoPE. Off lowers to exactly what stood here before.
    qk_norm: bool = False
    # Visibility by block (block diffusion, SDAR): key j is visible to
    # query i iff j // attn_block <= i // attn_block. 1 is the causal
    # rule. Above 1 the engine generates by denoising blocks of this
    # many tokens (engine/engine.py `_block_decode`), and the page size
    # must be a multiple of it so that prefix pages stay whole blocks.
    attn_block: int = 1
    # id that stands for a position not yet fixed (the checkpoint's
    # config.json `mask_token_id`; a model fact, read by the block burst)
    mask_token_id: int = -1
    # paged KV cache geometry
    page_size: int = 16
    max_pages_per_seq: int = 512          # context = page_size * this

    @property
    def context_length(self) -> int:
        return self.page_size * self.max_pages_per_seq

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-sized config (CPU-mesh friendly)."""
        defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        head_dim=16, page_size=4, max_pages_per_seq=16)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        defaults = dict(vocab_size=128256, hidden_size=4096,
                        intermediate_size=14336, num_layers=32, num_heads=32,
                        num_kv_heads=8, head_dim=128, rope_theta=500000.0)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama3_70b(cls, **kw) -> "LlamaConfig":
        defaults = dict(vocab_size=128256, hidden_size=8192,
                        intermediate_size=28672, num_layers=80, num_heads=64,
                        num_kv_heads=8, head_dim=128, rope_theta=500000.0)
        defaults.update(kw)
        return cls(**defaults)


def init_params(rng: jax.Array, cfg: LlamaConfig) -> dict:
    """Random-init params. Layer weights are stacked on a leading L axis for
    `lax.scan`. Shapes chosen so the "tp" shardings in engine/sharding.py
    split heads/ffn evenly. MoE configs dispatch to the expert-stacked
    layout (mixtral.init_moe_params) — callers (the engine, tests) get
    the right tree for any family from this one entry point."""
    if getattr(cfg, "num_experts", 0):
        from dynamo_tpu.models.mixtral import init_moe_params

        return init_moe_params(rng, cfg)
    E, F = cfg.hidden_size, cfg.intermediate_size
    H, KVH, D, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    k = iter(jax.random.split(rng, 12))

    def norm(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense(key, fan_in, *shape):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * scale).astype(cfg.dtype)

    # key-draw order matches the pre-bias layout (embed, wq..w_down,
    # lm_head, then biases) so seeded inits of bias-free configs are
    # unchanged across versions
    embed = dense(next(k), E, cfg.vocab_size, E)
    layers = {
        "attn_norm": norm(L, E),
        "wq": dense(next(k), E, L, E, H * D),
        "wk": dense(next(k), E, L, E, KVH * D),
        "wv": dense(next(k), E, L, E, KVH * D),
        "wo": dense(next(k), H * D, L, H * D, E),
        "mlp_norm": norm(L, E),
        "w_gate": dense(next(k), E, L, E, F),
        "w_up": dense(next(k), E, L, E, F),
        "w_down": dense(next(k), F, L, F, E),
    }
    lm_head = dense(next(k), E, E, cfg.vocab_size)
    if cfg.attention_bias:
        # nonzero so tests exercising the bias plumbing can't pass on a
        # silently-dropped bias
        layers["bq"] = dense(next(k), E, L, H * D)
        layers["bk"] = dense(next(k), E, L, KVH * D)
        layers["bv"] = dense(next(k), E, L, KVH * D)
    if cfg.qk_norm:
        # a key of its own: the twelve above are spoken for
        layers.update(init_qk_norm(jax.random.split(rng, 13)[12], cfg))
    return {
        "embed": embed,
        "layers": layers,
        "final_norm": norm(E),
        "lm_head": lm_head,
    }


def init_qk_norm(rng: jax.Array, cfg: LlamaConfig) -> dict:
    """q_norm / k_norm leaves (L, D) f32, uneven on purpose: a norm
    weight of all ones would let a dropped multiply pass the tests."""
    kq, kk = jax.random.split(rng)
    shape = (cfg.num_layers, cfg.head_dim)
    return {"q_norm": 1.0 + 0.1 * jax.random.normal(kq, shape, jnp.float32),
            "k_norm": 1.0 + 0.1 * jax.random.normal(kk, shape, jnp.float32)}


def init_cache(cfg: LlamaConfig, num_pages: int
               ) -> tuple[tuple[jax.Array, ...], tuple[jax.Array, ...]]:
    """(k_cache, v_cache): each a TUPLE of L per-layer arrays of shape
    (KVH, num_pages, page_size, D). Per-layer (not L-stacked) so every
    step's write is an in-place update — see module docstring. Page 0 is
    scratch."""
    shape = kv_layer_shape(cfg, num_pages)
    return (tuple(jnp.zeros(shape, dtype=cfg.dtype)
                  for _ in range(cfg.num_layers)),
            tuple(jnp.zeros(shape, dtype=cfg.dtype)
                  for _ in range(cfg.num_layers)))


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., T, H, D), positions: (..., T)."""
    d_half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(d_half, dtype=jnp.float32) / d_half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,T,d/2)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _write_kv(k_cache: jax.Array, v_cache: jax.Array, k: jax.Array,
              v: jax.Array, page_ids: jax.Array, offsets: jax.Array,
              valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Scatter new K/V vectors into the paged caches.

    caches: (KVH, N, P, D); k/v: (T, KVH, D); page_ids/offsets/valid: (T,).
    Padding lanes are redirected to scratch page 0 (never allocated for real
    sequences), so duplicate scatter targets can't race with real writes.

    On TPU the XLA scatter lowering dominates decode (~23ms/step measured on
    a 1B model), so a pallas block-DMA kernel (engine/kernels.py) is used
    when the geometry allows.
    """
    from dynamo_tpu.engine.attention import use_pallas
    from dynamo_tpu.engine.kernels import kv_write_supported, paged_kv_write

    safe_pages = jnp.where(valid, page_ids, 0)
    safe_offs = jnp.where(valid, offsets, 0)
    if use_pallas() and kv_write_supported(k_cache.shape[2], k.shape[-1]):
        return paged_kv_write(k_cache, v_cache, k, v, safe_pages, safe_offs)
    k_cache = k_cache.at[:, safe_pages, safe_offs, :].set(
        jnp.swapaxes(k, 0, 1))
    v_cache = v_cache.at[:, safe_pages, safe_offs, :].set(
        jnp.swapaxes(v, 0, 1))
    return k_cache, v_cache


def _swiglu(h: jax.Array, lp: dict) -> jax.Array:
    gate = jax.nn.silu(qm(h, lp["w_gate"]))
    return qm(gate * qm(h, lp["w_up"]), lp["w_down"])


def _mlp(h: jax.Array, lp: dict, cfg: "LlamaConfig") -> jax.Array:
    """THE per-layer FFN dispatch: dense SwiGLU for Llama/Qwen2
    families, top-k routed experts for MoE configs (mixtral.moe_mlp).
    cfg is static under jit, so the branch costs nothing at runtime —
    and because every forward flavor (paged prefill/decode, dense,
    pp stages) routes through here, an MoE config serves through the
    SAME engine/scheduler/spec/guided machinery as a dense model."""
    if getattr(cfg, "num_experts", 0):
        from dynamo_tpu.models.mixtral import moe_mlp

        return moe_mlp(h, lp, cfg)
    return _swiglu(h, lp)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _layer_params(params: dict, l: int) -> dict:
    """Static slice of layer l's weights from the L-stacked param arrays
    (free: XLA fuses the slice into the consuming matmul reads). A Pallas
    kernel is no such consumer: handed a slice it is handed a copy. So an
    MoE layer also carries `expert_stacks` = (the expert stacks of all
    layers, l), which the routed dispatch's kernel indexes by layer itself
    (engine/moe_gmm.py); the slices stay for every other reader and are
    dead code where nobody reads them."""
    layers = params["layers"]
    lp = jax.tree.map(lambda w: w[l], layers)
    if "router" in layers:
        lp["expert_stacks"] = (
            {k: layers[k] for k in ("w_gate", "w_up", "w_down")}, l)
    return lp


def qkv_proj(hn: jax.Array, lp: dict, cfg: LlamaConfig
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """q/k/v projections with the optional Qwen2-family additive bias —
    the ONE site every forward flavor (paged prefill/decode, dense,
    sp ring, pp stages) routes through, so a family's attention quirks
    can never diverge between serving paths."""
    q = qm(hn, lp["wq"])
    k = qm(hn, lp["wk"])
    v = qm(hn, lp["wv"])
    if cfg.attention_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    return q, k, v


def block_qkv(x: jax.Array, lp: dict, positions: jax.Array,
              cfg: LlamaConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """First half of THE transformer block, written once: what stands
    before the attention core (pre-norm, q/k/v projection, split into
    heads, RoPE). Every forward flavor (paged prefill / decode / mixed /
    ragged, dense, sp ring, pp stages, MoE) is `block_qkv`, its own core
    (its KV write and its attention, inline in its layer loop), then
    `block_out`. The core is NOT handed in as a callback: every Python
    frame between a jitted entry and the Pallas kernels it calls inline
    per layer costs tracing time at every start (measured on the chip's
    host, PERF.md §6 PR 35: four frames, +24% a decode trace).
    x: (..., E) with positions broadcastable to x.shape[:-1]; returns
    q (..., H, D) and k, v (..., KVH, D) (the LOCAL head counts under a
    manual tp shard)."""
    with jax.named_scope("attn_qkv"):
        hn = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = qkv_proj(hn, lp, cfg)
        heads = x.shape[:-1] + (-1, cfg.head_dim)
        q, k, v = q.reshape(heads), k.reshape(heads), v.reshape(heads)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
        if x.ndim == 2:
            # flat rows (one token each): rope wants a T axis
            q = rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
            k = rope(k[:, None], positions[:, None], cfg.rope_theta)[:, 0]
        else:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def block_out(x: jax.Array, attn: jax.Array, lp: dict, cfg: LlamaConfig,
              ffn=_mlp, reduce=lambda y: y) -> jax.Array:
    """Second half of THE transformer block: what follows the attention
    core (wo + residual, post-norm, FFN + residual). A flavor's true
    differences are parameters of its own call, never a branch on the
    caller in here: `ffn(h, lp, cfg)` is the FFN the caller chose
    (default: the config's own, `_mlp`); `reduce` is the caller's
    reduction over a manual tp shard (sp's psum), applied to both
    partial sums."""
    with jax.named_scope("attn_out"):
        x = x + reduce(qm(attn.reshape(x.shape[:-1] + (-1,)), lp["wo"]))
    with jax.named_scope("mlp"):
        hn = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + reduce(ffn(hn, lp, cfg))
    return x


def _chunk_kv(page_tables: jax.Array, cached_lens: jax.Array,
              seq_lens: jax.Array, T: int, cfg: LlamaConfig,
              aligned: bool):
    """A prefill chunk sub-batch's bookkeeping: positions (Bp, T) and
    `write(kc, vc, k, v)`, which stores the chunk's (Bp, T, KVH, D) K/V
    into the paged caches (whole pages through
    kernels.paged_kv_write_pages when `aligned` allows, else row by
    row; rows past seq_len go to scratch page 0)."""
    from dynamo_tpu.engine.attention import use_pallas
    from dynamo_tpu.engine.kernels import (
        kv_write_supported,
        paged_kv_write_pages,
    )

    Bp, P = page_tables.shape[0], cfg.page_size
    positions = cached_lens[:, None] + jnp.arange(T)[None, :]
    new_valid = positions < seq_lens[:, None]              # (Bp, T)
    page_ids = jnp.take_along_axis(
        page_tables, positions // P, axis=1)               # (Bp, T)
    offsets = positions % P

    def flat(a):
        return a.reshape((Bp * T,) + a.shape[2:])

    f_pages, f_offs, f_valid = flat(page_ids), flat(offsets), flat(new_valid)
    page_path = (aligned and T % P == 0 and use_pallas()
                 and kv_write_supported(P, kv_layer_shape(cfg, 1)[-1]))
    if page_path:
        # one destination page id per (seq, page-slot); slots entirely past
        # seq_len go to scratch 0
        slot_pages = jnp.where(new_valid[:, ::P], page_ids[:, ::P],
                               0).reshape(-1)             # (Bp*T/P,)

    def to_blocks(a):                      # (Bp,T,KVH,D) → (Bp*T/P,KVH,P,D)
        a = a.reshape((Bp, T // P, P) + a.shape[2:])
        return jnp.swapaxes(a, 2, 3).reshape(
            (Bp * (T // P), a.shape[3], P, a.shape[4]))

    def write(kc, vc, k, v):
        with jax.named_scope("kv_write"):
            if page_path:
                return paged_kv_write_pages(
                    kc, vc, to_blocks(k), to_blocks(v), slot_pages)
            return _write_kv(kc, vc, flat(k), flat(v), f_pages, f_offs,
                             f_valid)

    return positions, write


def _decode_kv(page_tables: jax.Array, positions: jax.Array,
               valid: jax.Array, cfg: LlamaConfig):
    """A decode sub-batch's bookkeeping: where each lane's one new K/V
    row goes (page_ids, offsets (B,)) and the attention lengths (B,;
    0 for padding lanes)."""
    page_ids = jnp.take_along_axis(
        page_tables, (positions // cfg.page_size)[:, None], axis=1)[:, 0]
    offsets = positions % cfg.page_size
    return page_ids, offsets, jnp.where(valid, positions + 1, 0)


def prefill_step(params: dict, k_cache: tuple, v_cache: tuple,
                 tokens: jax.Array, page_table: jax.Array,
                 cached_len: jax.Array, seq_len: jax.Array,
                 cfg: LlamaConfig) -> tuple[jax.Array, tuple, tuple]:
    """Prefill one sequence (bucket-padded length T): the Bp=1 special
    case of `prefill_batch` (single layer-body implementation — prefill
    numerics cannot diverge between the two).

    tokens: (T,) — the *uncached* suffix, padded; positions are
    cached_len..cached_len+T-1. page_table: (max_pages,). seq_len = total
    valid length (cached + new). Returns (logits_at_last (V,), k_cache,
    v_cache)."""
    logits, k_cache, v_cache = prefill_batch(
        params, k_cache, v_cache, tokens[None], page_table[None],
        jnp.asarray(cached_len)[None], jnp.asarray(seq_len)[None], cfg)
    return logits[0], k_cache, v_cache


def paged_forward(params: dict, k_cache: tuple, v_cache: tuple,
                  tokens: jax.Array, page_tables: jax.Array,
                  cached_lens: jax.Array, seq_lens: jax.Array,
                  cfg: LlamaConfig, aligned: bool = False
                  ) -> tuple[jax.Array, tuple, tuple]:
    """Paged multi-token forward shared by prefill and spec-verify
    (traceable): writes the chunk's KV into the paged caches, attends
    causally against cache + chunk (`paged_attention_prefill`: on the
    TPU the `paged_prefill_attention` kernel over the KV blocks each
    q tile can see; a spec-verify's few rows are a shape it declines,
    counted and served by the XLA einsum), returns the FINAL-NORMED
    hidden states for every position ((Bp, T, E), k_cache, v_cache) —
    callers pick which positions to project through lm_head."""
    x = params["embed"][tokens]                            # (Bp, T, E)
    positions, write = _chunk_kv(page_tables, cached_lens, seq_lens,
                                 tokens.shape[1], cfg, aligned)

    new_k, new_v = [], []
    for l in range(cfg.num_layers):
        lp = _layer_params(params, l)
        q, k, v = block_qkv(x, lp, positions, cfg)
        kc, vc = write(k_cache[l], v_cache[l], k, v)
        with jax.named_scope("attn_core"):
            attn = paged_attention_prefill(
                q, kc, vc, page_tables, cached_lens, seq_lens,
                page_size=cfg.page_size,
                attn_block=cfg.attn_block)                 # (Bp, T, H, D)
        x = block_out(x, attn, lp, cfg)
        new_k.append(kc)
        new_v.append(vc)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x, tuple(new_k), tuple(new_v)


@partial(jax.jit, static_argnames=("cfg", "aligned"), donate_argnums=(1, 2))
def prefill_batch(params: dict, k_cache: tuple, v_cache: tuple,
                  tokens: jax.Array, page_tables: jax.Array,
                  cached_lens: jax.Array, seq_lens: jax.Array,
                  cfg: LlamaConfig, aligned: bool = False
                  ) -> tuple[jax.Array, tuple, tuple]:
    """Prefill a BATCH of sequences' chunks in one device pass.

    tokens: (Bp, T) uncached suffix chunks (padded); page_tables:
    (Bp, max_pages); cached_lens/seq_lens: (Bp,). Returns (last-token
    logits (Bp, V), caches). One weight stream serves all Bp sequences —
    per-sequence prefill re-reads every weight per sequence, which
    dominated serving TTFT (measured 8.7 ms/seq vs ~10 ms for a whole
    batched round on the r2 bench model).

    Padding lanes (seq_len == cached_len) write only to scratch page 0 and
    produce garbage logits the engine ignores.

    `aligned` (static): caller guarantees every cached_len is a multiple
    of page_size AND T is — enabling the full-page store kernel
    (kernels.paged_kv_write_pages) instead of per-row writes.
    """
    x, k_cache, v_cache = paged_forward(
        params, k_cache, v_cache, tokens, page_tables, cached_lens,
        seq_lens, cfg, aligned)
    with jax.named_scope("lm_head"):
        last = jnp.maximum(seq_lens - cached_lens - 1, 0)  # (Bp,)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = qm(x_last, params["lm_head"])             # (Bp, V)
    return logits.astype(jnp.float32), k_cache, v_cache


def _decode_once(params: dict, k_cache: tuple, v_cache: tuple,
                 tokens: jax.Array, positions: jax.Array,
                 page_tables: jax.Array, valid: jax.Array,
                 cfg: LlamaConfig) -> tuple[jax.Array, tuple, tuple]:
    """One decode iteration body (traced; shared by single/multi-step).
    The logits leave as the head's product, in its dtype: the sampler
    reads them as they are, whoever computes on them widens."""
    x = params["embed"][tokens]                            # (B, E)
    page_ids, offsets, lengths = _decode_kv(page_tables, positions, valid,
                                            cfg)

    new_k, new_v = [], []
    for l in range(cfg.num_layers):
        lp = _layer_params(params, l)
        q, k, v = block_qkv(x, lp, positions, cfg)
        with jax.named_scope("kv_write"):
            kc, vc = _write_kv(k_cache[l], v_cache[l], k, v, page_ids,
                               offsets, valid)
        with jax.named_scope("attn_core"):
            attn = paged_attention_decode(
                q, kc, vc, lengths, page_tables, page_size=cfg.page_size)
        x = block_out(x, attn, lp, cfg)
        new_k.append(kc)
        new_v.append(vc)

    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = qm(x, params["lm_head"])                  # (B, V)
    return logits, tuple(new_k), tuple(new_v)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1, 2))
def decode_step(params: dict, k_cache: jax.Array, v_cache: jax.Array,
                tokens: jax.Array, positions: jax.Array,
                page_tables: jax.Array, valid: jax.Array,
                cfg: LlamaConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode iteration for a (bucket-padded) batch.

    tokens/positions/valid: (B,); page_tables: (B, max_pages).
    Returns (logits (B, V) fp32, k_cache, v_cache).
    """
    logits, k_cache, v_cache = _decode_once(
        params, k_cache, v_cache, tokens, positions, page_tables, valid, cfg)
    return logits.astype(jnp.float32), k_cache, v_cache


@partial(jax.jit, static_argnames=("cfg", "num_steps", "topk_lp"),
         donate_argnums=(1, 2))
def decode_multi_step(params: dict, k_cache: jax.Array, v_cache: jax.Array,
                      tokens: jax.Array, positions: jax.Array,
                      page_tables: jax.Array, valid: jax.Array,
                      seeds: jax.Array, steps0: jax.Array,
                      temperature: jax.Array, top_p: jax.Array,
                      top_k: jax.Array, cfg: LlamaConfig,
                      num_steps: int,
                      topk_lp: int = 0) -> tuple[jax.Array, jax.Array,
                                                 jax.Array]:
    """`num_steps` fused decode+sample iterations with ONE host round-trip.

    Host↔device syncs serialize the pipeline, so sampling runs on
    device and each sampled token feeds the next step directly. The host
    gets all `num_steps × B` tokens in a single transfer and applies stop
    conditions after the fact (bounded overshoot, reference-free tradeoff).

    Pages for positions..positions+num_steps-1 must be pre-allocated in
    `page_tables` (engine guarantees this). Returns
    (packed (2 + 2*topk_lp, num_steps, B) f32, k_cache, v_cache) where
    packed[0] is the sampled token ids (exact in f32: vocab « 2^24),
    packed[1] the chosen-token logprobs, and rows 2..2+topk_lp /
    2+topk_lp..2+2*topk_lp the top-k alternative ids/logprobs when
    topk_lp > 0 — PACKED so the host still pays exactly ONE transfer
    per burst (a second np.asarray would cost another full sync
    round-trip). topk_lp is static: the engine compiles the top-k
    variant only once some lane asks for alternatives, so the hot path
    never pays the (B, V) top-k when nobody wants it.
    """
    from dynamo_tpu.engine.sampling import (sample_with_logprob,
                                            topk_logprobs)

    def body(i, carry):
        toks, kc, vc, out = carry
        logits, kc, vc = _decode_once(
            params, kc, vc, toks, positions + i, page_tables, valid, cfg)
        with jax.named_scope("sample"):
            sampled, chosen = sample_with_logprob(
                logits, seeds, steps0 + i, temperature, top_p, top_k)
            out = out.at[0, i].set(sampled.astype(jnp.float32))
            out = out.at[1, i].set(chosen)
            if topk_lp:
                ids, vals = topk_logprobs(logits, topk_lp)
                out = lax.dynamic_update_slice(
                    out, ids.T[:, None, :], (2, i, 0))
                out = lax.dynamic_update_slice(
                    out, vals.T[:, None, :], (2 + topk_lp, i, 0))
        return sampled, kc, vc, out

    out0 = jnp.zeros((2 + 2 * topk_lp, num_steps, tokens.shape[0]),
                     dtype=jnp.float32)
    _, k_cache, v_cache, out = lax.fori_loop(
        0, num_steps, body, (tokens, k_cache, v_cache, out0))
    return out, k_cache, v_cache


def _block_forward(params: dict, k_cache: tuple, v_cache: tuple,
                   ids: jax.Array, pos0: jax.Array, page_tables: jax.Array,
                   valid: jax.Array, cfg: LlamaConfig, head: bool):
    """One forward of a block step: each lane's `attn_block` rows at
    positions pos0 .. pos0 + B - 1 (pos0 a multiple of B). Their K,V are
    written first (rewritten by every forward of the block: rollback
    through the page table is free), then all B rows see one and the same
    key set, everything up to the block's end, so they ride
    `paged_attention_decode` as B x groups query rows a kv head with
    lengths = pos0 + B: no kernel of their own. ids: (L, B); pos0, valid:
    (L,). Returns (logits (L, B, V) as the head's product, or None without
    `head`, caches)."""
    from dynamo_tpu.engine.attention import use_pallas
    from dynamo_tpu.engine.kernels import (kv_write_supported,
                                           paged_kv_write_block)

    lanes, blk = ids.shape
    x = params["embed"][ids]                               # (L, B, E)
    positions = pos0[:, None] + jnp.arange(blk)[None, :]   # (L, B)
    page_ids, offsets, _ = _decode_kv(page_tables, pos0, valid, cfg)
    lengths = jnp.where(valid, pos0 + blk, 0)
    safe_pages = jnp.where(valid, page_ids, 0)
    safe_offs = jnp.where(valid, offsets, 0)
    kernel_write = use_pallas() and kv_write_supported(cfg.page_size,
                                                       cfg.head_dim)

    kvh, g = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads

    new_k, new_v = [], []
    for l in range(cfg.num_layers):
        lp = _layer_params(params, l)
        q, k, v = block_qkv(x, lp, positions, cfg)
        with jax.named_scope("kv_write"):
            if kernel_write:
                kc, vc = paged_kv_write_block(
                    k_cache[l], v_cache[l], k, v, safe_pages, safe_offs)
            else:
                flat = (lanes * blk,) + k.shape[2:]
                kc, vc = _write_kv(
                    k_cache[l], v_cache[l], k.reshape(flat),
                    v.reshape(flat),
                    jnp.repeat(page_ids, blk),
                    (offsets[:, None] + jnp.arange(blk)).reshape(-1),
                    jnp.repeat(valid, blk))
        with jax.named_scope("attn_core"):
            # a kv head's rows: its B x groups queries, one after another
            q2 = q.reshape(lanes, blk, kvh, g, -1).transpose(
                0, 2, 1, 3, 4).reshape(lanes, kvh * blk * g, -1)
            attn = paged_attention_decode(
                q2, kc, vc, lengths, page_tables, page_size=cfg.page_size)
            attn = attn.reshape(lanes, kvh, blk, g, -1).transpose(
                0, 2, 1, 3, 4).reshape(q.shape)
        x = block_out(x, attn, lp, cfg)
        new_k.append(kc)
        new_v.append(vc)

    logits = None
    if head:
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"], cfg.rms_eps)
            # the product over flat rows, (L * B, V), is the array the
            # sampler reads; taken over (L, B, E) it is laid out position
            # major and the sampler's flat view costs a copy of the logits
            logits = qm(x.reshape(lanes * blk, -1),
                        params["lm_head"]).reshape(lanes, blk, -1)
    return logits, tuple(new_k), tuple(new_v)


def unmask_choice(masked: jax.Array, confidence: jax.Array, count: int,
                  strategy: str) -> jax.Array:
    """(L, B) bool: the `count` masked positions of each block a denoising
    step fixes. `sequential`: the leftmost. `low_confidence_static`: those
    whose best token has the highest probability, the leftmost of equals.
    Fewer than `count` masked: all of them."""
    if strategy == "sequential":
        rank = jnp.cumsum(masked, axis=-1) - 1
    else:
        score = jnp.where(masked, confidence, -jnp.inf)
        cols = jnp.arange(masked.shape[-1])
        ahead = (score[:, None, :] > score[:, :, None]) | (
            (score[:, None, :] == score[:, :, None])
            & (cols[None, None, :] < cols[None, :, None]))
        rank = jnp.sum(ahead, axis=-1)
    return masked & (rank < count)


@partial(jax.jit, static_argnames=("cfg", "num_blocks", "denoise_steps",
                                   "strategy"), donate_argnums=(1, 2))
def block_decode_multi_step(params: dict, k_cache: tuple, v_cache: tuple,
                            given: jax.Array, n_given: jax.Array,
                            positions: jax.Array, page_tables: jax.Array,
                            valid: jax.Array, seeds: jax.Array,
                            temperature: jax.Array, top_p: jax.Array,
                            top_k: jax.Array, cfg: LlamaConfig,
                            num_blocks: int, denoise_steps: int,
                            strategy: str
                            ) -> tuple[jax.Array, tuple, tuple]:
    """The decode burst of a block-diffusion model (`cfg.attn_block` = B
    > 1): `num_blocks` blocks a lane with ONE host round-trip, as
    `decode_multi_step` is `num_steps` tokens a lane.

    A block covers positions [p, p + B), p a multiple of B. Its state is
    the ids known there and `cfg.mask_token_id` elsewhere; whether a
    position is masked is carried as a boolean and never read off the id.
    Each of the `denoise_steps` forwards runs the block's state against
    the clean K,V of everything before it, rewrites the block's own K,V
    in place, and fixes B / denoise_steps of the masked positions
    (`unmask_choice`) with a token sampled from that position's logits
    (**logits at position i predict the token at position i**: no shift).
    One more forward over the final ids commits the block's K,V as the
    context of later blocks; it needs no logits and runs no lm_head.

    given: (L, B) ids already known at the head of each lane's FIRST block
    (a prompt's tail), n_given: (L,) how many; positions: (L,) where the
    first block starts. Pages for positions .. positions + num_blocks * B
    - 1 are pre-allocated. A token drawn at absolute position p of a lane
    uses the lane's seed at step p, so a stream does not depend on how
    bursts cut it. Returns (packed (2, num_blocks * B, L) f32: ids and the
    log-probability of the untempered logits of the step that fixed each,
    position-major as `decode_multi_step` packs its steps; the given
    positions carry their ids and 0; caches)."""
    from dynamo_tpu.engine.sampling import sample_with_logprob

    lanes, blk = given.shape
    per_step = blk // denoise_steps
    cols = jnp.arange(blk)

    def rep(a):
        return jnp.repeat(a, blk)

    def sample(logits, pos):
        """tokens, their log-probabilities and each row's confidence (the
        log-probability of its best token; asked of the sampler only by
        the strategy that reads it), (L, B) each."""
        got = [a.reshape(lanes, blk) for a in sample_with_logprob(
            logits.reshape(lanes * blk, -1), rep(seeds), pos.reshape(-1),
            rep(temperature), rep(top_p), rep(top_k),
            best=strategy == "low_confidence_static")]
        return got[0], got[1], got[-1]

    def block(b, carry):
        kc, vc, out = carry
        pos0 = positions + b * blk
        pos = pos0[:, None] + cols[None, :]
        known = (cols[None, :] < n_given[:, None]) & (b == 0)
        ids = jnp.where(known, given, cfg.mask_token_id).astype(jnp.int32)

        def denoise(_, c):
            ids, known, lps, kc, vc = c
            with jax.named_scope("denoise_step"):
                logits, kc, vc = _block_forward(
                    params, kc, vc, ids, pos0, page_tables, valid, cfg,
                    head=True)
                with jax.named_scope("sample"):
                    toks, chosen, conf = sample(logits, pos)
                    fix = unmask_choice(~known, conf, per_step, strategy)
                    ids = jnp.where(fix, toks, ids)
                    lps = jnp.where(fix, chosen, lps)
            return ids, known | fix, lps, kc, vc

        ids, _, lps, kc, vc = lax.fori_loop(
            0, denoise_steps, denoise,
            (ids, known, jnp.zeros((lanes, blk), jnp.float32), kc, vc))
        with jax.named_scope("block_commit"):
            _, kc, vc = _block_forward(
                params, kc, vc, ids, pos0, page_tables, valid, cfg,
                head=False)
        rows = jnp.stack([ids.T.astype(jnp.float32), lps.T])  # (2, B, L)
        return kc, vc, lax.dynamic_update_slice(out, rows, (0, b * blk, 0))

    out0 = jnp.zeros((2, num_blocks * blk, lanes), dtype=jnp.float32)
    k_cache, v_cache, out = lax.fori_loop(
        0, num_blocks, block, (k_cache, v_cache, out0))
    return out, k_cache, v_cache


def _mixed_forward(params: dict, k_cache: tuple, v_cache: tuple,
                   ch_tokens: jax.Array, ch_tables: jax.Array,
                   ch_cached: jax.Array, ch_seq_lens: jax.Array,
                   d_tokens: jax.Array, d_positions: jax.Array,
                   d_tables: jax.Array, d_valid: jax.Array,
                   cfg: LlamaConfig, aligned: bool
                   ) -> tuple[jax.Array, jax.Array, tuple, tuple]:
    """One fused layer sweep over a prefill chunk sub-batch AND one
    decode step: each layer's weight stream is read once and serves
    both sub-batches; attention routes through
    engine.attention.mixed_attention. The sub-batches are different
    sequences (disjoint page tables and disjoint KV write slots), and
    each side's ops mirror paged_forward / _decode_once exactly —
    separate matmuls per sub-batch, never a concatenated one — so the
    interleaving cannot perturb either side's numerics vs the
    stand-alone steps. Returns (chunk hidden (Bp, T, E) final-normed,
    decode hidden (B, E) final-normed, k_cache, v_cache)."""
    from dynamo_tpu.engine.attention import mixed_attention

    xc = params["embed"][ch_tokens]                        # (Bp, T, E)
    c_positions, write_c = _chunk_kv(ch_tables, ch_cached, ch_seq_lens,
                                     ch_tokens.shape[1], cfg, aligned)
    xd = params["embed"][d_tokens]                         # (B, E)
    d_page_ids, d_offsets, d_lengths = _decode_kv(d_tables, d_positions,
                                                  d_valid, cfg)

    new_k, new_v = [], []
    for l in range(cfg.num_layers):
        lp = _layer_params(params, l)
        q, k, v = block_qkv(xc, lp, c_positions, cfg)
        qd, kd, vd = block_qkv(xd, lp, d_positions, cfg)
        kc, vc = write_c(k_cache[l], v_cache[l], k, v)
        with jax.named_scope("kv_write"):
            kc, vc = _write_kv(kc, vc, kd, vd, d_page_ids, d_offsets,
                               d_valid)
        with jax.named_scope("attn_core"):
            attn_d, attn_c = mixed_attention(
                qd, q, kc, vc, d_lengths, d_tables, ch_tables, c_positions,
                ch_seq_lens, page_size=cfg.page_size)
        xc = block_out(xc, attn_c, lp, cfg)
        xd = block_out(xd, attn_d, lp, cfg)
        new_k.append(kc)
        new_v.append(vc)

    xc = rms_norm(xc, params["final_norm"], cfg.rms_eps)
    xd = rms_norm(xd, params["final_norm"], cfg.rms_eps)
    return xc, xd, tuple(new_k), tuple(new_v)


@partial(jax.jit,
         static_argnames=("cfg", "num_steps", "aligned", "topk_lp"),
         donate_argnums=(1, 2))
def mixed_prefill_decode(params: dict, k_cache: tuple, v_cache: tuple,
                         ch_tokens: jax.Array, ch_tables: jax.Array,
                         ch_cached: jax.Array, ch_seq_lens: jax.Array,
                         tokens: jax.Array, positions: jax.Array,
                         page_tables: jax.Array, valid: jax.Array,
                         seeds: jax.Array, steps0: jax.Array,
                         temperature: jax.Array, top_p: jax.Array,
                         top_k: jax.Array, cfg: LlamaConfig,
                         num_steps: int, aligned: bool = False,
                         topk_lp: int = 0
                         ) -> tuple[jax.Array, jax.Array, tuple, tuple]:
    """One jitted MIXED step: a prefill chunk sub-batch rides along with
    a full decode burst, so decode lanes keep emitting between a long
    prompt's chunks (the budgeted scheduler's device dispatch).

    Step 0 of the burst fuses with the chunk forward (_mixed_forward —
    one weight stream for both); steps 1..num_steps-1 are the plain
    fori_loop decode body. Sampling is exactly decode_multi_step's, so a
    lane's token stream is identical whether its burst ran mixed or
    plain. Chunk args are the prefill_batch batch arrays; decode args
    are the decode_multi_step arrays. Compile shapes bucket on
    (Bp pow2, T bucket) × the fixed decode width. Returns
    (packed (2 + 2*topk_lp, num_steps, B) f32, chunk last-token logits
    (Bp, V) f32, k_cache, v_cache)."""
    from dynamo_tpu.engine.sampling import (sample_with_logprob,
                                            topk_logprobs)

    xc, xd, k_cache, v_cache = _mixed_forward(
        params, k_cache, v_cache, ch_tokens, ch_tables, ch_cached,
        ch_seq_lens, tokens, positions, page_tables, valid, cfg, aligned)
    last = jnp.maximum(ch_seq_lens - ch_cached - 1, 0)     # (Bp,)
    x_last = jnp.take_along_axis(xc, last[:, None, None], axis=1)[:, 0]
    ch_logits = qm(x_last, params["lm_head"]).astype(jnp.float32)

    def sample(out, i, logits):
        sampled, chosen = sample_with_logprob(
            logits, seeds, steps0 + i, temperature, top_p, top_k)
        out = out.at[0, i].set(sampled.astype(jnp.float32))
        out = out.at[1, i].set(chosen)
        if topk_lp:
            ids, vals = topk_logprobs(logits, topk_lp)
            out = lax.dynamic_update_slice(
                out, ids.T[:, None, :], (2, i, 0))
            out = lax.dynamic_update_slice(
                out, vals.T[:, None, :], (2 + topk_lp, i, 0))
        return sampled, out

    out0 = jnp.zeros((2 + 2 * topk_lp, num_steps, tokens.shape[0]),
                     dtype=jnp.float32)
    sampled0, out0 = sample(out0, 0, qm(xd, params["lm_head"]))

    def body(i, carry):
        toks, kc, vc, out = carry
        logits, kc, vc = _decode_once(
            params, kc, vc, toks, positions + i, page_tables, valid, cfg)
        sampled, out = sample(out, i, logits)
        return sampled, kc, vc, out

    _, k_cache, v_cache, out = lax.fori_loop(
        1, num_steps, body, (sampled0, k_cache, v_cache, out0))
    return out, ch_logits, k_cache, v_cache


@partial(jax.jit, static_argnames=("cfg", "topk_lp"), donate_argnums=(1, 2))
def ragged_prefill_decode(params: dict, k_cache: tuple, v_cache: tuple,
                          tokens: jax.Array, positions: jax.Array,
                          page_ids: jax.Array, offsets: jax.Array,
                          valid: jax.Array, token_lanes: jax.Array,
                          lane_tables: jax.Array, ch_rows: jax.Array,
                          d_rows: jax.Array, seeds: jax.Array,
                          steps0: jax.Array, temperature: jax.Array,
                          top_p: jax.Array, top_k: jax.Array,
                          cfg: LlamaConfig, topk_lp: int = 0
                          ) -> tuple[jax.Array, jax.Array, tuple, tuple]:
    """THE flat-token ragged step: prefill chunk tokens and decode lanes
    ride one (Tb,) token array through one forward — no chunk rectangle,
    no pow2 decode width, no (Bp, T, k_steps, …) shape-zoo tuple. The
    only compile-shape dimension that varies is Tb, the total-token
    bucket (ch_rows/d_rows/sampling arrays are fixed at the engine's
    max_batch_size).

    tokens/positions/page_ids/offsets/valid/token_lanes: (Tb,) flat rows
    — each a chunk token or one decode lane's next token; padding rows
    have valid=False (KV redirects to scratch page 0, attention fully
    masked). lane_tables: (L, max_pages) page tables, one row per lane;
    rows are disjoint across sequences so cross-lane leakage is
    structurally impossible; within-chunk causality comes from the
    ragged mask (a row attends positions <= its own, and its K/V is
    written before attention — the `_decode_once` contract).
    ch_rows: (Bp,) flat row of each chunk's LAST token (→ ch_logits);
    d_rows: (B,) flat row of each decode lane (→ sampled). Sampling
    matches decode_multi_step's step exactly (same traced sampler, same
    steps0 indexing), so a lane's stream is identical whether its token
    came from a fused burst or a ragged round. Returns
    (packed (2 + 2*topk_lp, 1, B) f32 in the decode_multi_step layout,
    ch_logits (Bp, V) f32, k_cache, v_cache).
    """
    from dynamo_tpu.engine.attention import ragged_attention
    from dynamo_tpu.engine.sampling import (sample_with_logprob,
                                            topk_logprobs)

    x = params["embed"][tokens]                            # (Tb, E)
    qpos = jnp.where(valid, positions, -1).astype(jnp.int32)

    new_k, new_v = [], []
    for l in range(cfg.num_layers):
        lp = _layer_params(params, l)
        q, k, v = block_qkv(x, lp, positions, cfg)
        with jax.named_scope("kv_write"):
            kc, vc = _write_kv(k_cache[l], v_cache[l], k, v, page_ids,
                               offsets, valid)
        with jax.named_scope("attn_core"):
            attn = ragged_attention(q, kc, vc, qpos, token_lanes,
                                    lane_tables,
                                    page_size=cfg.page_size)   # (Tb, H, D)
        x = block_out(x, attn, lp, cfg)
        new_k.append(kc)
        new_v.append(vc)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    ch_logits = qm(x[ch_rows], params["lm_head"]).astype(jnp.float32)
    d_logits = qm(x[d_rows], params["lm_head"])

    sampled, chosen = sample_with_logprob(
        d_logits, seeds, steps0, temperature, top_p, top_k)
    out = jnp.zeros((2 + 2 * topk_lp, 1, d_rows.shape[0]),
                    dtype=jnp.float32)
    out = out.at[0, 0].set(sampled.astype(jnp.float32))
    out = out.at[1, 0].set(chosen)
    if topk_lp:
        ids, vals = topk_logprobs(d_logits, topk_lp)
        out = lax.dynamic_update_slice(out, ids.T[:, None, :], (2, 0, 0))
        out = lax.dynamic_update_slice(
            out, vals.T[:, None, :], (2 + topk_lp, 0, 0))
    return out, ch_logits, tuple(new_k), tuple(new_v)


@partial(jax.jit, static_argnames=("cfg", "num_steps", "topk_lp"),
         donate_argnums=(1, 2))
def decode_multi_step_guided(params: dict, k_cache, v_cache,
                             tokens: jax.Array, positions: jax.Array,
                             page_tables: jax.Array, valid: jax.Array,
                             seeds: jax.Array, steps0: jax.Array,
                             temperature: jax.Array, top_p: jax.Array,
                             top_k: jax.Array, min_p: jax.Array,
                             rep_pen: jax.Array, freq_pen: jax.Array,
                             pres_pen: jax.Array,
                             prompt_counts: jax.Array,
                             out_counts: jax.Array, g_bits: jax.Array,
                             g_next: jax.Array, g_eos_ok: jax.Array,
                             g_ids: jax.Array, g_states: jax.Array,
                             stop_ids: jax.Array, cfg: LlamaConfig,
                             num_steps: int, topk_lp: int = 0):
    """The CONSTRAINED decode burst: `decode_multi_step` plus everything
    the plain hot path doesn't pay for — grammar masks, min_p, and the
    OpenAI/HF sampling penalties — enforced ON DEVICE so constrained
    lanes keep the fused one-sync-per-burst contract. The engine routes
    a batch here when ANY lane needs any of it (slot 0 is the trivial
    all-allowed grammar, penalty values of 1/0 are no-ops).

    min_p/rep_pen/freq_pen/pres_pen: (B,); prompt_counts/out_counts:
    (B, V) token histograms (out_counts advances on device as tokens
    sample, so within-burst repeats are penalized too).

    g_bits: (G, S, ceil(V/8)) uint8 packed allowed-token masks;
    g_next: (G, S, V) int16 DFA transition; g_eos_ok: (G, S) bool —
    where the lane's STOP tokens become legal (grammar satisfied, or a
    dead end that must terminate); g_ids/g_states: (B,) lane grammar
    slot + current DFA state (slot 0 is the trivial all-allowed grammar
    for unguided lanes); stop_ids: (B, K) the lane's stop token ids
    (-1 padding). Disallowed tokens' logits are pushed to -1e30 BEFORE
    sampling (greedy and stochastic), and each sampled token advances
    its lane's DFA state for the next iteration (llm/guided.py builds
    the tables; the engine recomputes authoritative states host-side
    from the emitted tokens)."""
    from dynamo_tpu.engine.sampling import (
        constrained_logits,
        sample_with_logprob,
        stop_token_mask,
    )

    V = cfg.vocab_size
    B = tokens.shape[0]
    is_stop = stop_token_mask(stop_ids, V)                # (B, V)

    def body(i, carry):
        toks, st, counts, kc, vc, out = carry
        logits, kc, vc = _decode_once(
            params, kc, vc, toks, positions + i, page_tables, valid, cfg)
        logits = constrained_logits(
            logits.astype(jnp.float32), prompt_counts, counts, rep_pen,
            freq_pen, pres_pen, g_bits, g_eos_ok, g_ids, st, is_stop)
        sampled, chosen = sample_with_logprob(
            logits, seeds, steps0 + i, temperature, top_p, top_k, min_p)
        st = g_next[g_ids, st, sampled].astype(jnp.int32)
        counts = counts.at[jnp.arange(B), sampled].add(
            valid.astype(counts.dtype))
        out = out.at[0, i].set(sampled.astype(jnp.float32))
        out = out.at[1, i].set(chosen)
        if topk_lp:
            # alternatives come from the same post-penalty post-mask
            # logits the lane sampled from (what "the distribution"
            # means for a constrained lane)
            from dynamo_tpu.engine.sampling import topk_logprobs

            tk_ids, tk_vals = topk_logprobs(logits, topk_lp)
            out = lax.dynamic_update_slice(
                out, tk_ids.T[:, None, :], (2, i, 0))
            out = lax.dynamic_update_slice(
                out, tk_vals.T[:, None, :], (2 + topk_lp, i, 0))
        return sampled, st, counts, kc, vc, out

    out0 = jnp.zeros((2 + 2 * topk_lp, num_steps, tokens.shape[0]),
                     dtype=jnp.float32)
    _, _, _, k_cache, v_cache, out = lax.fori_loop(
        0, num_steps, body,
        (tokens, g_states.astype(jnp.int32), out_counts, k_cache,
         v_cache, out0))
    return out, k_cache, v_cache


def dense_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                 mask: jax.Array) -> jax.Array:
    """The attention core of the cache-free forwards (embeddings, MoE
    parity forward, pipeline-parallel stages): GQA attention of a dense
    (unpaged) sequence under ``mask`` (T, T) bool, so the dense
    attention math exists exactly once outside the paged path.
    q: (B, T, H, D); k, v: (B, T, KVH, D)."""
    H, KVH, D = q.shape[-2], k.shape[-2], q.shape[-1]
    if KVH != H:
        k = jnp.repeat(k, H // KVH, axis=2)
        v = jnp.repeat(v, H // KVH, axis=2)
    with jax.named_scope("attn_core"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(D))
        scores = jnp.where(mask[None, None], scores, -1e30)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
            v.astype(jnp.float32)).astype(q.dtype)


def dense_layer(x: jax.Array, lp: dict, positions: jax.Array,
                mask: jax.Array, cfg: LlamaConfig, **out_kw) -> jax.Array:
    """One cache-free layer: the block around `dense_attend`."""
    q, k, v = block_qkv(x, lp, positions, cfg)
    return block_out(x, dense_attend(q, k, v, mask), lp, cfg, **out_kw)


@partial(jax.jit, static_argnames=("cfg",))
def embed_batch(params: dict, tokens: jax.Array, lengths: jax.Array,
                cfg: "LlamaConfig") -> jax.Array:
    """Mean-pooled sentence embeddings: (B, T) padded prompts + (B,)
    valid lengths → (B, E) L2-normalized vectors.

    Dense cache-free forward (embeddings never decode, so no paged KV):
    `dense_layer` per layer, final rms_norm, masked
    mean over valid positions. Serves `/v1/embeddings` for the real
    engine (openai.rs:1125 parity; the reference delegates to an
    embedding engine — we own ours)."""
    B, T = tokens.shape
    positions = jnp.arange(T)[None, :]
    valid = positions < lengths[:, None]                    # (B, T)
    # padding lanes attend only within the valid prefix
    mask = jnp.tril(jnp.ones((T, T), bool))
    x = params["embed"][tokens]
    for l in range(cfg.num_layers):
        x = dense_layer(x, _layer_params(params, l), positions, mask, cfg)
    h = rms_norm(x, params["final_norm"], cfg.rms_eps).astype(jnp.float32)
    h = jnp.where(valid[..., None], h, 0.0)
    pooled = h.sum(axis=1) / jnp.maximum(
        lengths[:, None].astype(jnp.float32), 1.0)
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-12)
