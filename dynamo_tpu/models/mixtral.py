"""Mixtral-style MoE transformer: top-k routed experts, expert-parallel.

Reference parity: the reference serves MoE models (DeepSeek-R1 wideep
recipes, `recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml:
60-63`) by delegating EP to the engine; here the engine is ours, so the
expert layout is native. TPU-first formulation:

- Routing: softmax over the top-k router logits (`moe_route`).
- Where the experts are local (one device, a pp stage) the FFN is ROUTED:
  rows sorted by expert, one grouped product a projection over the stack
  (engine/moe_gmm.py), unsorted and combined. Static shapes (each
  expert's run padded to whole row tiles), the routed FLOPs, nothing
  dropped, and only the experts some token chose are read.
- With the expert axis sharded over an "ep" mesh axis the FFN stays ONE
  batched einsum over the expert axis with a per-token weight mask
  (`_moe_mlp_dense`): GSPMD partitions it so each chip computes ITS
  experts, then inserts one psum to combine. Compute is num_experts/k×
  the routed FLOPs there (an exchange of routed rows is the next step).
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
    init_qk_norm,
    rms_norm,
)


@dataclasses.dataclass(frozen=True)
class MoeConfig(LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2
    # Facts of a checkpoint's config.json about its expert layers; the
    # defaults are the Mixtral / Qwen3-MoE layer and lower to what stood
    # here before. `router_scoring`: "softmax" (gates = softmax over the
    # k best logits) or "sigmoid" (scores sigmoid(logits); the k experts
    # are chosen by score + the learned `router_bias`, weighted by the
    # UNBIASED scores renormalised over the chosen, their sum plus
    # `router_norm_eps` (1e-20 Nemotron-H's, 1e-6 LFM2's), and times
    # `routed_scaling`). `expert_act`: "swiglu" (gate and up projections)
    # or "relu2" (un-gated: down(relu(up(x))^2), no w_gate leaf); either
    # goes with either scoring.
    # `shared_expert_size` > 0: one more expert of that width, un-gated
    # relu2 (the one form served), added for every token (w_shared_up /
    # w_shared_down).
    router_scoring: str = "softmax"
    router_norm_eps: float = 1e-20
    routed_scaling: float = 1.0
    expert_act: str = "swiglu"
    shared_expert_size: int = 0

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

    # drawn last, so seeded inits of configs without it are unchanged
    qk = init_qk_norm(jax.random.split(rng, 13)[12], cfg) \
        if cfg.qk_norm else {}
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
            **qk,
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


def _experts_sharded() -> bool:
    """True while tracing under a mesh with an 'ep' axis of more than one
    device (the engine dispatches inside `jax.set_mesh`): the expert axis
    of the stacks is then split by GSPMD and the dense mask below is the
    form it partitions."""
    mesh = jax.sharding.get_abstract_mesh()
    return (not mesh.empty) and mesh.shape.get("ep", 1) > 1


def moe_route(h: jax.Array, lp: dict, cfg: MoeConfig
              ) -> tuple[jax.Array, jax.Array]:
    """(gates (..., T, k) f32, experts (..., T, k) i32): each token's k
    best experts by the router's logits and their weights, the softmax
    over those k logits. That equals the softmax over all experts
    renormalised over the k chosen (`norm_topk_prob`): exp(l_e) / sum over
    the chosen of exp(l), whichever sum came first."""
    if cfg.router_scoring == "sigmoid":
        return _route_sigmoid(h, lp, cfg)
    router_logits = (h @ lp["router"]).astype(jnp.float32)  # (..., T, X)
    topv, topi = jax.lax.top_k(router_logits, cfg.experts_per_token)
    return jax.nn.softmax(topv, axis=-1), topi


def _route_sigmoid(h: jax.Array, lp: dict, cfg: MoeConfig
                   ) -> tuple[jax.Array, jax.Array]:
    """Sigmoid routing with a learned correction (`router_bias`, (X,)):
    the bias moves the CHOICE of the k experts and never their weights,
    which are the unbiased scores over their sum, times `routed_scaling`.
    Scores in float32 at full matmul precision: the k-th and (k+1)-th
    biased scores can lie close, and a bf16 product would choose another
    expert than the model's."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), lp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))               # (..., T, X)
    _, topi = jax.lax.top_k(scores + lp["router_bias"],
                            cfg.experts_per_token)
    chosen = jnp.take_along_axis(scores, topi, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                      + cfg.router_norm_eps)
    return gates * cfg.routed_scaling, topi


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def moe_mlp(h: jax.Array, lp: dict, cfg: MoeConfig) -> jax.Array:
    """Top-k routed expert FFN. h: (..., T, E) → (..., T, E).

    Routed dispatch wherever the experts are local (one device, a pp
    stage's slice): the k x T routed rows are laid out expert by expert
    and meet the stacks in one grouped product each for gate, up and down
    (engine/moe_gmm.py), so the work is the routed work and an expert no
    token chose is never read. No token is dropped. Expert stacks may be
    int8 QTensors (weight-only, engine quantize="int8"), read as int8.
    Under an 'ep' mesh the dense mask (`_moe_mlp_dense`) is kept: the
    choice is by what the mesh is."""
    if _experts_sharded():
        return _moe_mlp_dense(h, lp, cfg)
    from dynamo_tpu.engine.moe_gmm import (grouped_matmul, padded_rows,
                                           route_layout, row_tile)

    k, n_exp = cfg.experts_per_token, cfg.num_experts
    gated = cfg.expert_act == "swiglu"
    flat = h.reshape(-1, h.shape[-1])                       # (T, E)
    routed = flat.shape[0] * k
    tile = row_tile(routed, n_exp, *lp["w_up"].shape[-2:])
    with jax.named_scope("moe_route"):
        gates, topi = moe_route(flat, lp, cfg)              # (T, k)
        pos, tile_expert, n_used, sizes = route_layout(
            topi.reshape(-1), n_exp, tile)
        # the token each row of the padded layout holds (padding: token 0,
        # computed and never read)
        src = jnp.zeros(padded_rows(routed, n_exp, tile), jnp.int32).at[
            pos].set(jnp.arange(routed, dtype=jnp.int32) // k)
    stacks, layer = lp.get("expert_stacks", (None, 0))

    def product(rows, name):
        return grouped_matmul(
            rows, lp[name], tile_expert, n_used, sizes, tile,
            layers=None if stacks is None else (stacks[name], layer))

    with jax.named_scope("moe_experts"):
        rows = flat[src]                                    # (M, E)
        if gated:
            gate = jax.nn.silu(product(rows, "w_gate"))
            down = product(gate * product(rows, "w_up"), "w_down")
        else:
            down = product(_relu2(product(rows, "w_up")), "w_down")
    if cfg.shared_expert_size:
        with jax.named_scope("moe_shared"):
            shared = qm(_relu2(qm(flat, lp["w_shared_up"])),
                        lp["w_shared_down"])
    with jax.named_scope("moe_combine"):
        per_token = down[pos].reshape(flat.shape[0], k, -1)
        out = jnp.einsum("tke,tk->te", per_token.astype(jnp.float32),
                         gates)
        if cfg.shared_expert_size:
            out = out + shared.astype(jnp.float32)
        out = out.astype(h.dtype)
    return out.reshape(h.shape)


def _moe_mlp_dense(h: jax.Array, lp: dict, cfg: MoeConfig) -> jax.Array:
    """Dense dispatch: every expert computes every token, the top-k
    softmax weight mask zeroes the rest. The expert axis ('x' below) is the
    EP sharding axis — under a mesh with the expert dims of w_gate/up/down
    sharded over "ep", GSPMD computes each chip's experts locally and
    psums the weighted combine. Compute is num_experts / k times the
    routed work: the form for a sharded expert axis only."""
    gates, topi = moe_route(h, lp, cfg)                     # (..., T, k)
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
