"""Mixtral-style MoE transformer: top-k routed experts, expert-parallel.

Reference parity: the reference serves MoE models (DeepSeek-R1 wideep
recipes, `recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml:
60-63`) by delegating EP to the engine; here the engine is ours, so the
expert layout is native. TPU-first formulation:

- Routing is computed densely (softmax over router logits, top-k mask).
- Expert FFNs are evaluated as ONE batched einsum over the expert axis
  with a per-token weight mask — no gather/scatter, no dynamic shapes,
  so XLA tiles it straight onto the MXU. Compute cost is num_experts/k×
  the routed FLOPs; with the expert axis sharded over an "ep" mesh axis
  GSPMD partitions that einsum so each chip only computes ITS experts,
  then inserts one psum to combine — the classic all-gathered-activation
  EP layout (good up to moderate expert counts; a capacity-based
  all-to-all dispatch is the next step when expert count × tokens grows).
- Attention/norms/embedding reuse the Llama blocks unchanged.

`ep_param_specs()` gives the PartitionSpecs (expert axis → "ep"); the
same dict composes with "tp" specs on a 2-D ("ep", "tp") mesh by
sharding each expert's FFN hidden dim over "tp".
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine.quant import qm
from dynamo_tpu.models.llama import (
    LlamaConfig,
    _layer_params,
    dense_layer,
    rms_norm,
)


@dataclasses.dataclass(frozen=True)
class MoeConfig(LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2

    @classmethod
    def tiny(cls, **kw) -> "MoeConfig":
        defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        head_dim=16, page_size=4, max_pages_per_seq=16,
                        num_experts=4, experts_per_token=2)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MoeConfig":
        defaults = dict(vocab_size=32000, hidden_size=4096,
                        intermediate_size=14336, num_layers=32,
                        num_heads=32, num_kv_heads=8, head_dim=128,
                        rope_theta=1e6, num_experts=8, experts_per_token=2)
        defaults.update(kw)
        return cls(**defaults)


def init_moe_params(rng: jax.Array, cfg: MoeConfig) -> dict:
    """Like llama.init_params but the MLP is per-expert weight stacks
    (L, X, E, F) plus a router (L, E, X)."""
    E, F, X = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    H, KVH, D, L = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                    cfg.num_layers)
    k = iter(jax.random.split(rng, 12))

    def norm(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense(key, fan_in, *shape):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * scale).astype(cfg.dtype)

    return {
        "embed": dense(next(k), E, cfg.vocab_size, E),
        "layers": {
            "attn_norm": norm(L, E),
            "wq": dense(next(k), E, L, E, H * D),
            "wk": dense(next(k), E, L, E, KVH * D),
            "wv": dense(next(k), E, L, E, KVH * D),
            "wo": dense(next(k), H * D, L, H * D, E),
            "mlp_norm": norm(L, E),
            "router": dense(next(k), E, L, E, X),
            "w_gate": dense(next(k), E, L, X, E, F),
            "w_up": dense(next(k), E, L, X, E, F),
            "w_down": dense(next(k), F, L, X, F, E),
        },
        "final_norm": norm(E),
        "lm_head": dense(next(k), E, E, cfg.vocab_size),
    }


def _qe(subscripts: str, x: jax.Array, w) -> jax.Array:
    """Einsum against a maybe-quantized expert stack — qm's analog for
    the (X, in, out) expert weights. W8A16 only (the int8 convert fuses
    into the operand read, per-channel scale multiplies the output);
    w8a8/int4 expert kernels don't exist yet. The bits check lives
    HERE (not just the engine's cfg.quantize guard) because
    pre-quantized param trees reach this code without passing through
    that guard — and einsumming nibble-packed int4 bytes as int8
    weights would produce silently garbage logits."""
    from dynamo_tpu.engine.quant import QTensor

    if isinstance(w, QTensor):
        if w.bits != 8:
            raise ValueError(
                f"int{w.bits} expert stacks unsupported (W8A16 only)")
        y = jnp.einsum(subscripts, x, w.q.astype(x.dtype))
        # s: (X, 1, out) per-channel over the contraction dim → (X, out)
        # broadcasts over the (..., T, X, out) einsum output
        return y * w.s[:, 0, :].astype(x.dtype)
    return jnp.einsum(subscripts, x, w)


def moe_mlp(h: jax.Array, lp: dict, cfg: MoeConfig) -> jax.Array:
    """Top-k routed expert FFN. h: (..., T, E) → (..., T, E).

    Dense-dispatch: every expert computes every token, the top-k softmax
    weight mask zeroes the rest. The expert axis ('x' below) is the EP
    sharding axis — under a mesh with the expert dims of w_gate/up/down
    sharded over "ep", GSPMD computes each chip's experts locally and
    psums the weighted combine. Expert stacks may be int8 QTensors
    (weight-only; engine quantize="int8") — with ep=8 that puts
    Mixtral-8x7B experts at ~5.9 GB/chip, inside a v5e."""
    router_logits = (h @ lp["router"]).astype(jnp.float32)  # (..., T, X)
    k = cfg.experts_per_token
    topv, topi = jax.lax.top_k(router_logits, k)            # (..., T, k)
    gates = jax.nn.softmax(topv, axis=-1)                   # (..., T, k)
    # scatter the k gate weights back to a dense (..., T, X) mask
    dense_w = jnp.sum(
        jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32)
        * gates[..., None], axis=-2)                        # (..., T, X)
    gate = jax.nn.silu(_qe("...te,xef->...txf", h, lp["w_gate"]))
    up = _qe("...te,xef->...txf", h, lp["w_up"])
    down = _qe("...txf,xfe->...txe", gate * up, lp["w_down"])
    out = jnp.einsum("...txe,...tx->...te", down,
                     dense_w.astype(down.dtype))
    return out


def moe_mlp_capacity(h: jax.Array, lp: dict, cfg: MoeConfig,
                     capacity_factor: float = 1.25) -> jax.Array:
    """Capacity-based (GShard-style) expert dispatch. h: (B, T, E).

    Each expert processes at most C = ceil(T·k/X · capacity_factor)
    tokens; earlier tokens win slots, overflow tokens are DROPPED (their
    residual connection passes the hidden state through unchanged —
    standard Switch/GShard semantics). FLOPs are the ROUTED cost
    (≈ k·T·capacity_factor tokens of FFN) instead of dense-dispatch's
    X·T, which is what makes large expert counts viable.

    All-to-all ready: the dispatch einsum 'btxc,bte->bxce' maps token-
    dimension data onto the expert dimension — under a mesh where the
    expert weight axis is sharded over "ep" (and tokens over "dp"/"sp"),
    GSPMD lowers exactly that contraction to the expert all-to-all the
    reference's wideep recipes get from DeepEP, then partitions the FFN
    per chip and psums the combine."""
    B, T, E = h.shape
    X, k = cfg.num_experts, cfg.experts_per_token
    C = max(k, int(math.ceil(T * k / X * capacity_factor)))
    router_logits = (h @ lp["router"]).astype(jnp.float32)  # (B, T, X)
    topv, topi = jax.lax.top_k(router_logits, k)            # (B, T, k)
    gates = jax.nn.softmax(topv, axis=-1)                   # (B, T, k)

    # slot assignment: flatten choices token-major ((t, j) → s = t*k+j) so
    # earlier tokens claim expert slots first; exclusive cumsum per expert
    # gives each choice its position within the expert's capacity. Only
    # (B, S, X) and (B, T, k, ·) intermediates are materialized — the
    # (·, X, C) cross product appears once, contracted straight into the
    # (B, T, X, C) dispatch/combine the einsums need.
    sel = jax.nn.one_hot(topi, X, dtype=jnp.float32)        # (B, T, k, X)
    sel_flat = sel.reshape(B, T * k, X)
    pos = jnp.cumsum(sel_flat, axis=1) - sel_flat           # (B, S, X)
    # position of each (t, j) choice within ITS chosen expert
    pos_tk = jnp.sum(pos * sel_flat, axis=-1).reshape(B, T, k)
    keep = (pos_tk < C).astype(jnp.float32)                 # (B, T, k)
    slot = jax.nn.one_hot(pos_tk.astype(jnp.int32), C,
                          dtype=jnp.float32)                # (B, T, k, C)
    # collapse the k slots onto tokens (top-k indices are distinct, so a
    # token never occupies two slots of the same expert)
    dispatch_t = jnp.einsum("btkx,btkc->btxc",
                            sel * keep[..., None], slot)
    combine_t = jnp.einsum("btkx,btkc->btxc",
                           sel * (keep * gates)[..., None], slot)

    hf = h.astype(jnp.float32)
    xin = jnp.einsum("btxc,bte->bxce", dispatch_t, hf).astype(h.dtype)
    gate = jax.nn.silu(jnp.einsum("bxce,xef->bxcf", xin, lp["w_gate"]))
    up = jnp.einsum("bxce,xef->bxcf", xin, lp["w_up"])
    down = jnp.einsum("bxcf,xfe->bxce", gate * up, lp["w_down"])
    return jnp.einsum("btxc,bxce->bte", combine_t,
                      down.astype(jnp.float32)).astype(h.dtype)


def moe_mlp_reference(h: jax.Array, lp: dict, cfg: MoeConfig) -> jax.Array:
    """Per-token loop reference (slow, obviously-correct) for tests."""
    import numpy as np

    hn = np.asarray(h, dtype=np.float32)
    flat = hn.reshape(-1, hn.shape[-1])
    out = np.zeros_like(flat)
    router = np.asarray(lp["router"], dtype=np.float32)
    for t in range(flat.shape[0]):
        logits = flat[t] @ router
        top = np.argsort(-logits)[: cfg.experts_per_token]
        ex = np.exp(logits[top] - logits[top].max())
        gates = ex / ex.sum()
        for g, x in zip(gates, top):
            wg = np.asarray(lp["w_gate"][x], dtype=np.float32)
            wu = np.asarray(lp["w_up"][x], dtype=np.float32)
            wd = np.asarray(lp["w_down"][x], dtype=np.float32)
            a = flat[t] @ wg
            silu = a / (1.0 + np.exp(-a))
            out[t] += g * ((silu * (flat[t] @ wu)) @ wd)
    return out.reshape(hn.shape)


@partial(jax.jit, static_argnames=("cfg", "dispatch", "capacity_factor"))
def moe_forward(params: dict, tokens: jax.Array, cfg: MoeConfig,
                dispatch: str = "dense",
                capacity_factor: float = 1.25) -> jax.Array:
    """Full-sequence forward (no KV cache): last-token logits (B, V).
    The serving engine reuses llama's paged machinery; this entry is the
    EP-shardable forward used for parity tests and the multichip dryrun.
    dispatch: "dense" (mask-weighted, all experts compute all tokens) or
    "capacity" (GShard-style all-to-all dispatch, routed FLOPs only;
    capacity_factor tunes drop rate vs FLOPs)."""
    if dispatch not in ("dense", "capacity"):
        raise ValueError(f"unknown dispatch mode {dispatch!r}")
    T = tokens.shape[1]
    positions = jnp.arange(T)[None, :]
    x = params["embed"][tokens]
    mask = jnp.tril(jnp.ones((T, T), bool))
    if dispatch == "dense":
        mlp = moe_mlp
    else:
        mlp = partial(moe_mlp_capacity, capacity_factor=capacity_factor)
    for l in range(cfg.num_layers):
        x = dense_layer(x, _layer_params(params, l), positions, mask, cfg,
                        ffn=mlp)
    xf = rms_norm(x[:, -1], params["final_norm"], cfg.rms_eps)
    return qm(xf, params["lm_head"]).astype(jnp.float32)


def ep_param_specs() -> dict:
    """PartitionSpecs for init_moe_params' tree: expert axis over "ep",
    everything else replicated (compose with tp by mapping the F dims)."""
    return {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, None),
            "wk": P(None, None, None),
            "wv": P(None, None, None),
            "wo": P(None, None, None),
            "mlp_norm": P(None, None),
            "router": P(None, None, None),
            "w_gate": P(None, "ep", None, None),
            "w_up": P(None, "ep", None, None),
            "w_down": P(None, "ep", None, None),
        },
        "final_norm": P(None),
        "lm_head": P(None, None),
    }
