"""LFM2-MoE family (`model_type: lfm2_moe`): a stack whose layers are an
operator and an FFN, x = x + op(rms(x)), x = x + ffn(rms(x)), each of two
kinds, named by `layer_types` and `num_dense_layers`:

    conv   gated short convolution   [B | C | z] = in_proj(u) ; g = B * z
                                     c_t = sum_j k_j g_{t-(K-1)+j} ; out_proj(C * c)
    attn   GQA attention             q/k RMS norm a head, then RoPE; Wo attn(q, k, v)
    dense  SwiGLU                    W2 (silu(W1 u) * W3 u)        (the first layers)
    moe    routed SwiGLU experts     sigmoid scores, the k best of score + bias,
                                     weights the unbiased scores over their sum + 1e-6

Parameters are stacked BY KIND (`layers.conv`, `.attn`, `.dense`, `.moe`)
and a static table (`Lfm2MoeConfig.table`) sends layer l to (operator kind,
its index within the kind, FFN kind, its index, index of its cache pair).
The layer loop is Python over that table, kernels inline under the jitted
entry, as models/nemotron_h.py.

State. The convolution is depthwise and K = 3 taps wide: a sequence keeps,
a conv layer, the inputs of its two older taps, g_{t-2} and g_{t-1}, each
hidden_size wide. Neither grows with the context, so neither is paged: a
sequence owns a SLOT (engine/pages.py `SlotPool`, `state_shapes`) from
admission to its end, and the entries take `slots` beside `page_tables`.
Slot 0 is scratch: invalid lanes and padding rows point at it and leave it
zero. The arrays ride in the cache tuples the entries donate, one pair a
layer in layer order: (older (S, E), newer (S, E)) for a conv layer, (K
pages, V pages) for an attention layer. A first chunk starts from zero
whatever its slot holds; a chunk's new state is its last two REAL inputs.

Attention heads are 64 wide: two kv heads ride side by side in a 128-lane
row of the cache (`kv_fold`; engine/pages.py `kv_layer_shape`,
engine/attention.py `folded`), the model's own bytes a token and a row the
repo's kernels tile.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.attention import (paged_attention_decode,
                                         paged_attention_prefill)
from dynamo_tpu.engine.pages import kv_layer_shape, state_shapes
from dynamo_tpu.engine.quant import qm
from dynamo_tpu.models.llama import (_chunk_kv, _decode_kv, _swiglu,
                                     _write_kv, block_qkv, rms_norm)
from dynamo_tpu.models.mixtral import MoeConfig, moe_mlp

OPERATORS = {"conv": "conv", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(MoeConfig):
    """Model facts of an `lfm2_moe` config.json. `intermediate_size` is
    the routed experts' width, `dense_size` the leading dense FFNs';
    `operators` names each layer's operator ("conv" / "attn")."""
    operators: tuple = ("conv", "attn", "conv")
    num_dense_layers: int = 1
    dense_size: int = 128
    conv_kernel: int = 3
    qk_norm: bool = True
    router_scoring: str = "sigmoid"
    router_norm_eps: float = 1e-6
    expert_act: str = "swiglu"

    # the module whose entries serve this configuration
    # (models/__init__.py `family_module`); a per-sequence state beside the
    # pages
    entries_module = "dynamo_tpu.models.lfm2_moe"
    recurrent = True

    def __post_init__(self):
        if len(self.operators) != self.num_layers \
                or set(self.operators) - set(OPERATORS.values()):
            raise ValueError(
                f"operators {self.operators!r} must name {self.num_layers} "
                f"layers out of {sorted(set(OPERATORS.values()))}")
        if self.conv_kernel != 3:
            raise ValueError("lfm2_moe: a convolution of 3 taps "
                             "(conv_L_cache 3) is the one served")

    @property
    def table(self) -> tuple:
        """layer -> (operator kind, index within it, FFN kind, index
        within it, index of its cache pair)."""
        seen = {"conv": 0, "attn": 0}
        out = []
        for l, op in enumerate(self.operators):
            dense = l < self.num_dense_layers
            out.append((op, seen[op], "dense" if dense else "moe",
                        l if dense else l - self.num_dense_layers, l))
            seen[op] += 1
        return tuple(out)

    def count(self, kind: str) -> int:
        return sum(1 for op in self.operators if op == kind)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def state_layers(self) -> int:
        return self.count("conv")

    @property
    def slot_state(self) -> tuple:
        """What a slot holds a conv layer (engine/pages.py
        `state_shapes`): g_{t-2} and g_{t-1}, in the activations' dtype."""
        return (((self.hidden_size,), None), ((self.hidden_size,), None))

    @property
    def kv_fold(self) -> int:
        """kv heads side by side in one row of the cache: two where a
        head is 64 wide (engine/pages.py `kv_layer_shape`)."""
        return 2 if self.head_dim == 64 and self.num_kv_heads % 2 == 0 \
            else 1


def config_from_hf(hf: dict, **overrides) -> Lfm2MoeConfig:
    """Lfm2MoeConfig from a checkpoint's config.json keys. A kind of layer
    this family does not serve is refused by name."""
    types = list(hf["layer_types"])[:hf["num_hidden_layers"]]
    unknown = sorted(set(types) - set(OPERATORS))
    if unknown or len(types) != hf["num_hidden_layers"]:
        raise ValueError(
            f"lfm2_moe: layer_types names {unknown or len(types)}; the "
            f"kinds served are {sorted(OPERATORS)}, one a layer")
    if hf.get("conv_bias") or not hf.get("norm_topk_prob", True) \
            or not hf.get("use_expert_bias", True):
        raise ValueError("lfm2_moe: only the layout without a convolution "
                         "bias, with an expert bias and norm_topk_prob is "
                         "served")
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    cfg = dict(
        vocab_size=hf["vocab_size"], hidden_size=hidden,
        intermediate_size=int(hf["moe_intermediate_size"]),
        dense_size=int(hf["intermediate_size"]),
        num_dense_layers=int(hf["num_dense_layers"]),
        num_layers=len(types),
        operators=tuple(OPERATORS[t] for t in types),
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hidden // heads,
        rope_theta=float(hf.get("rope_theta", 1e6)),
        rms_eps=float(hf.get("norm_eps", 1e-5)),
        num_experts=int(hf["num_experts"]),
        experts_per_token=int(hf["num_experts_per_tok"]),
        routed_scaling=float(hf.get("routed_scaling_factor", 1.0)),
        conv_kernel=int(hf.get("conv_L_cache", 3)))
    cfg.update(overrides)
    return Lfm2MoeConfig(**cfg)


# ---------------------------------------------------------------------------
# Parameters and state
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: Lfm2MoeConfig) -> dict:
    """Random-init params, stacked by kind. Taps of order 1, so that the
    state matters; a router bias as large as the gaps between scores, so
    that choice by biased and weight by unbiased score differ; q/k norm
    weights uneven. The head is the embedding's transpose (tied)."""
    E, F, Fd, X = (cfg.hidden_size, cfg.intermediate_size, cfg.dense_size,
                   cfg.num_experts)
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Lc, La = cfg.count("conv"), cfg.count("attn")
    Ld, Le = cfg.num_dense_layers, cfg.num_moe_layers
    keys = iter(jax.random.split(rng, 24))

    def dense(fan_in, *shape, dtype=None):
        w = jax.random.normal(next(keys), shape, jnp.float32) \
            / math.sqrt(fan_in)
        return w.astype(dtype or cfg.dtype)

    def norm(*shape):
        return jnp.ones(shape, jnp.float32)

    def uneven(*shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    embed = dense(E, cfg.vocab_size, E)
    return {
        "embed": embed,
        "layers": {
            "conv": {
                "op_norm": norm(Lc, E),
                "in_proj": dense(E, Lc, E, 3 * E),
                "conv_w": dense(1, Lc, cfg.conv_kernel, E,
                                dtype=jnp.float32),
                "out_proj": dense(E, Lc, E, E),
            },
            "attn": {
                "attn_norm": norm(La, E),
                "wq": dense(E, La, E, H * D),
                "wk": dense(E, La, E, KVH * D),
                "wv": dense(E, La, E, KVH * D),
                "wo": dense(H * D, La, H * D, E),
                "q_norm": uneven(La, D),
                "k_norm": uneven(La, D),
            },
            "dense": {
                "ffn_norm": norm(Ld, E),
                "w_gate": dense(E, Ld, E, Fd),
                "w_up": dense(E, Ld, E, Fd),
                "w_down": dense(Fd, Ld, Fd, E),
            },
            "moe": {
                "ffn_norm": norm(Le, E),
                "router": 4.0 * dense(E, Le, E, X, dtype=jnp.float32),
                "router_bias": 0.2 * jax.random.normal(
                    next(keys), (Le, X), jnp.float32),
                "w_gate": dense(E, Le, X, E, F),
                "w_up": dense(E, Le, X, E, F),
                "w_down": dense(F, Le, X, F, E),
            },
        },
        "final_norm": uneven(E),
        "lm_head": jnp.transpose(embed),
    }


def init_cache(cfg: Lfm2MoeConfig, num_pages: int, num_slots: int = 2
               ) -> tuple[tuple, tuple]:
    """(k_cache, v_cache): one pair a layer, in layer order. A conv
    layer's pair is (g_{t-2}, g_{t-1}), each (S, E), `num_slots` = S slots
    with slot 0 scratch; an attention layer's pair is its K and V pages in
    the format engine/pages.py answers. Each its own array, so every update
    is in place."""
    state = state_shapes(cfg, num_slots)
    kv = kv_layer_shape(cfg, num_pages)
    pairs = [tuple(jnp.zeros(*s) for s in state) if op == "conv"
             else (jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype))
             for op in cfg.operators]
    return tuple(p[0] for p in pairs), tuple(p[1] for p in pairs)


def _layer_params(params: dict, kind: str, i: int) -> dict:
    """Static slice of layer i of its kind. An expert layer also carries
    `expert_stacks`, which the grouped product's kernel indexes by layer
    itself (models/llama.py `_layer_params`)."""
    stack = params["layers"][kind]
    lp = jax.tree.map(lambda w: w[i], stack)
    if kind == "moe":
        lp["expert_stacks"] = (
            {k: stack[k] for k in ("w_gate", "w_up", "w_down")}, i)
    return lp


# ---------------------------------------------------------------------------
# Operators
# ---------------------------------------------------------------------------


def _conv_inputs(hn, lp: dict):
    """(g = B * z, C) of the normed input: the convolution's input and the
    gate on its output."""
    with jax.named_scope("conv_in_proj"):
        b, c, z = jnp.split(qm(hn, lp["in_proj"]), 3, axis=-1)
        return b * z, c


def _conv_out(c, mixed, lp: dict):
    with jax.named_scope("conv_out_proj"):
        return qm((c.astype(jnp.float32) * mixed).astype(c.dtype),
                  lp["out_proj"])


def conv_prefill(hn, lp: dict, older, newer, slots, cached_lens, seq_lens):
    """A gated short convolution over a round of prefill chunks. hn
    (Bp, T, E) the normed input; older, newer (S, E) the layer's state,
    g_{t-2} and g_{t-1} of each slot's sequence. A first chunk
    (`cached_lens == 0`) starts from zero whatever its slot holds; the new
    state is the last two REAL inputs (a chunk of one token keeps g_{t-1}
    as its g_{t-2}), so the padded end of a bucket never enters it.
    Returns (out (Bp, T, E), older, newer)."""
    t = hn.shape[1]
    g, c = _conv_inputs(hn, lp)
    with jax.named_scope("conv_mix"):
        fresh = (cached_lens == 0)[:, None]
        window = jnp.concatenate(
            [jnp.where(fresh, 0, older[slots])[:, None],
             jnp.where(fresh, 0, newer[slots])[:, None], g], axis=1)
        wf = window.astype(jnp.float32)                      # (Bp, T + 2, E)
        mixed = sum(lp["conv_w"][j] * lax.slice_in_dim(wf, j, j + t, axis=1)
                    for j in range(3))
        n_real = seq_lens - cached_lens                      # (Bp,)
        last = jnp.take_along_axis(
            window, (n_real[:, None] + jnp.arange(2)[None, :])[..., None],
            axis=1)                                          # (Bp, 2, E)
        older = older.at[slots].set(last[:, 0])
        newer = newer.at[slots].set(last[:, 1])
    return _conv_out(c, mixed, lp), older, newer


def conv_decode(hn, lp: dict, older, newer, slots, valid):
    """A gated short convolution, one token a lane: the state shifted in
    place at the lane's slot. hn (B, E). Invalid lanes point at slot 0 and
    leave it as it is."""
    g, c = _conv_inputs(hn, lp)
    with jax.named_scope("conv_mix"):
        g2, g1 = older[slots], newer[slots]
        w = lp["conv_w"]
        mixed = w[0] * g2.astype(jnp.float32) \
            + w[1] * g1.astype(jnp.float32) + w[2] * g.astype(jnp.float32)
        keep = valid[:, None]
        older = older.at[slots].set(jnp.where(keep, g1, g2))
        newer = newer.at[slots].set(jnp.where(keep, g, g1))
    return _conv_out(c, mixed, lp), older, newer


def _rows(x, cfg: Lfm2MoeConfig):
    """k or v (..., KVH, D) as the cache's rows (..., KVH / fold,
    fold * D): neighbouring heads side by side."""
    return x.reshape(x.shape[:-2] + kv_layer_shape(cfg, 1)[::3])


def _attn_out(x, attn, lp: dict):
    with jax.named_scope("attn_out"):
        return x + qm(attn.reshape(x.shape[:-1] + (-1,)), lp["wo"])


def _ffn(x, params: dict, kind: str, i: int, cfg: Lfm2MoeConfig):
    lp = _layer_params(params, kind, i)
    with jax.named_scope("mlp"):
        hn = rms_norm(x, lp["ffn_norm"], cfg.rms_eps)
        return x + (moe_mlp(hn, lp, cfg) if kind == "moe"
                    else _swiglu(hn, lp))


# ---------------------------------------------------------------------------
# Entries (the names the engine dispatches and the readers search)
# ---------------------------------------------------------------------------


def _paged_forward(params, k_cache, v_cache, tokens, page_tables,
                   cached_lens, seq_lens, slots, cfg, aligned):
    x = params["embed"][tokens]                              # (Bp, T, E)
    positions, write = _chunk_kv(page_tables, cached_lens, seq_lens,
                                 tokens.shape[1], cfg, aligned)
    first, second = list(k_cache), list(v_cache)
    for op, i, ffn, j, ci in cfg.table:
        lp = _layer_params(params, op, i)
        if op == "conv":
            out, first[ci], second[ci] = conv_prefill(
                rms_norm(x, lp["op_norm"], cfg.rms_eps), lp, first[ci],
                second[ci], slots, cached_lens, seq_lens)
            x = x + out
        else:
            q, k, v = block_qkv(x, lp, positions, cfg)
            first[ci], second[ci] = write(first[ci], second[ci],
                                          _rows(k, cfg), _rows(v, cfg))
            with jax.named_scope("attn_core"):
                attn = paged_attention_prefill(
                    q, first[ci], second[ci], page_tables, cached_lens,
                    seq_lens, page_size=cfg.page_size)
            x = _attn_out(x, attn, lp)
        x = _ffn(x, params, ffn, j, cfg)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x, tuple(first), tuple(second)


@partial(jax.jit, static_argnames=("cfg", "aligned"), donate_argnums=(1, 2))
def prefill_batch(params: dict, k_cache: tuple, v_cache: tuple,
                  tokens: jax.Array, page_tables: jax.Array,
                  cached_lens: jax.Array, seq_lens: jax.Array,
                  cfg: Lfm2MoeConfig, aligned: bool = False, *,
                  slots: jax.Array) -> tuple[jax.Array, tuple, tuple]:
    """models/llama.py `prefill_batch` for this family: a round of prefill
    chunks, `slots` (Bp,) beside `page_tables` (a padding row: slot 0 and
    `seq_len == cached_len`). Returns (last-token logits (Bp, V), caches)."""
    x, k_cache, v_cache = _paged_forward(
        params, k_cache, v_cache, tokens, page_tables, cached_lens,
        seq_lens, slots, cfg, aligned)
    with jax.named_scope("lm_head"):
        last = jnp.maximum(seq_lens - cached_lens - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = qm(x_last, params["lm_head"])
    return logits.astype(jnp.float32), k_cache, v_cache


def _decode_once(params, k_cache, v_cache, tokens, positions, page_tables,
                 valid, slots, cfg):
    x = params["embed"][tokens]                              # (B, E)
    page_ids, offsets, lengths = _decode_kv(page_tables, positions, valid,
                                            cfg)
    first, second = list(k_cache), list(v_cache)
    for op, i, ffn, j, ci in cfg.table:
        lp = _layer_params(params, op, i)
        if op == "conv":
            out, first[ci], second[ci] = conv_decode(
                rms_norm(x, lp["op_norm"], cfg.rms_eps), lp, first[ci],
                second[ci], slots, valid)
            x = x + out
        else:
            q, k, v = block_qkv(x, lp, positions, cfg)
            with jax.named_scope("kv_write"):
                first[ci], second[ci] = _write_kv(
                    first[ci], second[ci], _rows(k, cfg), _rows(v, cfg),
                    page_ids, offsets, valid)
            with jax.named_scope("attn_core"):
                attn = paged_attention_decode(
                    q, first[ci], second[ci], lengths, page_tables,
                    page_size=cfg.page_size)
            x = _attn_out(x, attn, lp)
        x = _ffn(x, params, ffn, j, cfg)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = qm(x, params["lm_head"])
    # as the head's product: the sampler reads it as it is
    return logits, tuple(first), tuple(second)


@partial(jax.jit, static_argnames=("cfg", "num_steps", "topk_lp"),
         donate_argnums=(1, 2))
def decode_multi_step(params: dict, k_cache: tuple, v_cache: tuple,
                      tokens: jax.Array, positions: jax.Array,
                      page_tables: jax.Array, valid: jax.Array,
                      seeds: jax.Array, steps0: jax.Array,
                      temperature: jax.Array, top_p: jax.Array,
                      top_k: jax.Array, cfg: Lfm2MoeConfig,
                      num_steps: int, topk_lp: int = 0, *,
                      slots: jax.Array) -> tuple[jax.Array, tuple, tuple]:
    """models/llama.py `decode_multi_step` for this family: `num_steps`
    fused decode + sample iterations, one host round trip, `slots` (B,)
    beside `page_tables` (an invalid lane: slot 0). Same packed output."""
    from dynamo_tpu.engine.sampling import (sample_with_logprob,
                                            topk_logprobs)

    def body(i, carry):
        toks, kc, vc, out = carry
        logits, kc, vc = _decode_once(
            params, kc, vc, toks, positions + i, page_tables, valid, slots,
            cfg)
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
                     jnp.float32)
    _, k_cache, v_cache, out = lax.fori_loop(
        0, num_steps, body, (tokens, k_cache, v_cache, out0))
    return out, k_cache, v_cache


@partial(jax.jit, static_argnames=("cfg",))
def forward_logits(params: dict, tokens: jax.Array, cfg: Lfm2MoeConfig
                   ) -> jax.Array:
    """Every position's logits (T, V) of ONE sequence in one pass: the
    prefill entry over fresh state and pages of its own. For tests."""
    t = tokens.shape[0]
    pages = -(-t // cfg.page_size)
    kc, vc = init_cache(cfg, pages + 1, 2)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    x, _, _ = _paged_forward(
        params, kc, vc, tokens[None], table, jnp.zeros(1, jnp.int32),
        jnp.full(1, t, jnp.int32), jnp.ones(1, jnp.int32), cfg, False)
    return qm(x[0], params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Checkpoint (`model.layers.{i}.{conv,self_attn,feed_forward}.*`)
# ---------------------------------------------------------------------------

# ours -> the checkpoint's tensor under `model.layers.{i}.`, by kind;
# `t`: stored (out, in), transposed on the way in; `f32`: kept float32
_TENSORS = {
    "conv": (("op_norm", "operator_norm.weight", "f32"),
             ("in_proj", "conv.in_proj.weight", "t"),
             ("conv_w", "conv.conv.weight", "conv"),
             ("out_proj", "conv.out_proj.weight", "t")),
    "attn": (("attn_norm", "operator_norm.weight", "f32"),
             ("wq", "self_attn.q_proj.weight", "t"),
             ("wk", "self_attn.k_proj.weight", "t"),
             ("wv", "self_attn.v_proj.weight", "t"),
             ("wo", "self_attn.out_proj.weight", "t"),
             ("q_norm", "self_attn.q_layernorm.weight", "f32"),
             ("k_norm", "self_attn.k_layernorm.weight", "f32")),
    "dense": (("ffn_norm", "ffn_norm.weight", "f32"),
              ("w_gate", "feed_forward.w1.weight", "t"),
              ("w_up", "feed_forward.w3.weight", "t"),
              ("w_down", "feed_forward.w2.weight", "t")),
    "moe": (("ffn_norm", "ffn_norm.weight", "f32"),
            ("router", "feed_forward.gate.weight", "tf32"),
            ("router_bias", "feed_forward.expert_bias", "f32")),
}
_EXPERTS = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))


def checkpoint_names(cfg: Lfm2MoeConfig) -> list:
    """Every tensor of the checkpoint, in the order `load_params` reads.
    The head is the embedding (`tie_word_embeddings`): no lm_head tensor."""
    names = []
    for layer, (op, _, ffn, _, _) in enumerate(cfg.table):
        p = f"model.layers.{layer}."
        for kind in (op, ffn):
            names += [p + name for _, name, _ in _TENSORS[kind]]
        if ffn == "moe":
            names += [p + f"feed_forward.experts.{e}.{w}.weight"
                      for _, w in _EXPERTS for e in range(cfg.num_experts)]
    return names + ["model.embed_tokens.weight",
                    "model.embedding_norm.weight"]


def load_params(path: str, cfg: Lfm2MoeConfig, quantize=None) -> dict:
    """Checkpoint -> param pytree on the default device, as
    models/nemotron_h.py `load_params`: reads on a prefetch thread,
    transpose / cast / int8 on the device tensor by tensor (quantize before
    stack, so transients stay int8). The head is the embedding's transpose,
    a copy of its own (models/loader.py does the same for a tied llama)."""
    from dynamo_tpu.engine.quant import (QUANT_KEYS, QTensor,
                                         _lm_head_quant_ok, quantize as q8)
    from dynamo_tpu.models.loader import _Prefetcher, _TensorIndex

    if quantize not in (None, False, "int8"):
        raise ValueError("lfm2_moe serves bf16 or weight-only int8")
    idx = _TensorIndex(path)
    pf = _Prefetcher(idx, checkpoint_names(cfg))
    pending = []

    def throttle(out):
        pending.append(out)
        if len(pending) >= 8:
            jax.block_until_ready(pending.pop())
            pending.clear()
        return out

    @partial(jax.jit, static_argnames=("how",))
    def prep(w, how):
        if how == "conv":                       # (E, 1, K) -> (K, E)
            return jnp.transpose(w[:, 0, :]).astype(jnp.float32)
        if how in ("t", "tf32"):
            w = jnp.transpose(w)
        return w.astype(jnp.float32 if how in ("f32", "tf32")
                        else cfg.dtype)

    quant = jax.jit(q8, donate_argnums=(0,))

    def tensor(name, how, key):
        w = throttle(prep(jax.device_put(pf.get(name)), how))
        if quantize and key in QUANT_KEYS:
            w = quant(w)
            throttle(w.q)
        return w

    def stack(ws):
        if isinstance(ws[0], QTensor):
            return QTensor(q=jnp.stack([w.q for w in ws]),
                           s=jnp.stack([w.s for w in ws]))
        return jnp.stack(ws)

    @partial(jax.jit, donate_argnums=(0,))
    def put(stacks, w, layer, expert):
        """Expert `expert` of expert layer `layer` written into the stacks
        of all layers, in place."""
        return jax.tree.map(
            lambda buf, piece: lax.dynamic_update_slice(
                buf, piece[None, None], (layer, expert) + (0,) * piece.ndim),
            stacks, w)

    try:
        by_kind = {kind: {key: [] for key, _, _ in _TENSORS[kind]}
                   for kind in _TENSORS}
        # the expert stacks are laid out once, whole, and filled expert by
        # expert: stacked from their pieces (a layer's 32, then the 22
        # layers) a stack, its pieces and the copies `jnp.stack` makes on
        # the way are alive together, 16.0 of the chip's 16.9 GB against
        # 8.6 of weights (my chip runs, PR 48)
        experts = {}
        for layer, (op, _, ffn, j, _) in enumerate(cfg.table):
            p = f"model.layers.{layer}."
            for kind in (op, ffn):
                for key, name, how in _TENSORS[kind]:
                    by_kind[kind][key].append(tensor(p + name, how, key))
            if ffn == "moe":
                for key, name in _EXPERTS:
                    for e in range(cfg.num_experts):
                        w = tensor(p + f"feed_forward.experts.{e}.{name}"
                                   ".weight", "t", key)
                        if key not in experts:
                            experts[key] = jax.tree.map(
                                lambda a: jnp.zeros(
                                    (cfg.num_moe_layers, cfg.num_experts)
                                    + a.shape, a.dtype), w)
                        experts[key] = put(experts[key], w, j, e)
        layers = {kind: {key: stack(ws) for key, ws in d.items()}
                  for kind, d in by_kind.items()}
        layers["moe"].update(experts)
        embed = tensor("model.embed_tokens.weight", "", "embed")
        params = {
            "layers": layers, "embed": embed,
            "final_norm": tensor("model.embedding_norm.weight", "f32",
                                 "norm"),
        }
        lm = throttle(jnp.transpose(embed))
        params["lm_head"] = quant(lm) \
            if quantize and _lm_head_quant_ok(lm) else lm
        jax.block_until_ready(params)
        return params
    finally:
        pf.stop()
        idx.close()
