"""Sequence-parallel (ring-attention) long-context prefill for Llama.

The reference has no SP/CP at all (SURVEY §2.10: "absent — relies on
engine TP and KVBM offload"); on TPU we own the engine, so long prompts
shard over a mesh "sp" axis: every device embeds and projects ITS chunk
of the prompt (activations never materialize globally), attention runs as
a K/V ring (`engine/ring_attention.py`), and the MLP is pointwise over
sequence so it needs no communication at all. Peak activation memory per
chip drops by ~sp×, which is what bounds single-chip prefill length.

Composes with tensor parallelism: pass ``tp_axis`` under a 2-D
("sp", "tp") mesh and the per-chunk projections shard heads/ffn/vocab
over "tp" exactly as the standard path does — inside shard_map the
megatron collectives are explicit (masked-embed psum, psums after
wo/w_down), since GSPMD doesn't insert them for manual shards.

Outputs: last-token logits (what serving needs to start decode) plus each
layer's K/V for the sequence — still sequence-sharded, ready to be paged
into the engine cache chunk-by-chunk without ever gathering the full
sequence on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.quant import qm
from dynamo_tpu.engine.ring_attention import ring_attention_local
from dynamo_tpu.models.llama import (
    LlamaConfig,
    _layer_params,
    block_out,
    block_qkv,
    rms_norm,
)


def _sp_forward_local(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                      axis: str, layout: str = "contiguous",
                      tp_axis=None):
    """Per-shard body (inside shard_map): tokens (B, Tc) local chunk.

    With ``tp_axis`` the mesh is 2-D ("sp", "tp") and each shard holds
    1/tp of the heads/ffn/vocab — shard_map means collectives are
    MANUAL here: masked-embed psum, megatron psums after wo/w_down.
    Head counts below are then the LOCAL counts.

    Returns (logits (1, B, V_local) — this shard's LAST-token logits,
    k_all, v_all (L, B, Tc, KVH_local, D) — this chunk's KV for cache
    writeback)."""
    from dynamo_tpu.engine.ring_attention import zigzag_positions

    idx = lax.axis_index(axis)
    sp_size = lax.psum(1, axis)
    Tc = tokens.shape[1]
    if layout == "zigzag":
        positions = zigzag_positions(idx, Tc, sp_size)[None, :]
    else:
        positions = (idx * Tc + jnp.arange(Tc))[None, :]   # global positions
    if tp_axis:
        # vocab-sharded embedding: masked local lookup + psum
        v_local = params["embed"].shape[0]
        local = tokens - lax.axis_index(tp_axis) * v_local
        ok = (local >= 0) & (local < v_local)
        x = jnp.where(ok[..., None],
                      params["embed"][jnp.clip(local, 0, v_local - 1)],
                      0)
        x = lax.psum(x, tp_axis)
    else:
        x = params["embed"][tokens]                        # (B, Tc, E)

    def reduce_tp(y):
        return lax.psum(y, tp_axis) if tp_axis else y

    ks, vs = [], []
    for l in range(cfg.num_layers):
        lp = _layer_params(params, l)
        q, k, v = block_qkv(x, lp, positions, cfg)
        ks.append(k)
        vs.append(v)
        attn = ring_attention_local(q, k, v, axis, causal=True,
                                    layout=layout)
        x = block_out(x, attn, lp, cfg, reduce=reduce_tp)
    xf = rms_norm(x[:, -1], params["final_norm"], cfg.rms_eps)
    logits = qm(xf, params["lm_head"]).astype(jnp.float32)
    return logits[None], jnp.stack(ks), jnp.stack(vs)


def _param_in_specs(params, tp_axis):
    """shard_map in_specs matching the param treedef: replicated for the
    1-D ring; megatron tp specs (engine/sharding.param_specs) for the
    2-D mesh. QTensor leaves need a (q, s)-shaped spec node — a QTensor
    HOLDING PartitionSpecs flattens identically."""
    if tp_axis is None:
        return jax.tree.map(lambda _: P(), params)
    from dynamo_tpu.engine.quant import QTensor, scale_spec
    from dynamo_tpu.engine.sharding import specs_for

    def spec_of(x, s):
        if isinstance(x, QTensor):
            # bits must match the param QTensor's aux or the spec tree's
            # treedef diverges from the arg tree's under shard_map
            return QTensor(q=s, s=scale_spec(s, x.s.ndim), bits=x.bits,
                           act_bits=x.act_bits)
        return s

    return jax.tree.map(spec_of, params, specs_for(params),
                        is_leaf=lambda x: not isinstance(x, dict))


@functools.partial(jax.jit,
                   static_argnames=("cfg", "mesh", "axis", "layout",
                                    "tp_axis"))
def _sp_prefill_jit(params, tokens, cfg: LlamaConfig, mesh: Mesh,
                    axis: str, layout: str = "contiguous", tp_axis=None):
    fn = shard_map(
        functools.partial(_sp_forward_local, cfg=cfg, axis=axis,
                          layout=layout, tp_axis=tp_axis),
        mesh=mesh,
        in_specs=(_param_in_specs(params, tp_axis), P(None, axis)),
        out_specs=(P(axis, None, tp_axis),
                   P(None, None, axis, tp_axis, None),
                   P(None, None, axis, tp_axis, None)))
    return fn(params, tokens)


def sp_prefill(params: dict, tokens: jax.Array, cfg: LlamaConfig,
               mesh: Mesh, axis: str = "sp", layout: str = "contiguous",
               kv_order: str = "natural", tp_axis=None):
    """Sequence-parallel prefill of a long prompt.

    tokens: (B, T) with T divisible by the "sp" axis size (2× that for
    layout="zigzag", which balances causal work across the ring — see
    engine/ring_attention.py). Returns (last_logits (B, V) float32,
    k_all, v_all (L, B, T, KVH, D) — KV sequence-sharded over the mesh).

    kv_order (zigzag only): "natural" un-permutes the KV to token order —
    convenient, but the permutation makes XLA ALL-GATHER the full-T KV
    onto every chip, defeating sp's memory point on a real ring. Callers
    that gather to one device anyway (the engine's cache writeback)
    should pass "ring" and apply `zigzag_permutation`'s inverse locally
    after their own gather.

    Params are replicated over "sp" (each chip streams the weights once
    per its chunk). With ``tp_axis`` on a 2-D ("sp", "tp") mesh, params
    must be placed with the megatron tp specs (engine/sharding): heads,
    ffn and vocab shard over tp and the ring runs per tp shard, with
    explicit psums after wo/w_down — the multi-host layout where weights
    don't fit one chip (requires H, KVH, F, V divisible by tp)."""
    from dynamo_tpu.engine.ring_attention import zigzag_permutation

    if kv_order not in ("natural", "ring"):
        raise ValueError(f"unknown kv_order {kv_order!r}")
    if tp_axis is not None and tp_axis != "tp":
        # the megatron in_specs come from engine/sharding.param_specs,
        # which names the weight-sharding axis "tp"; a differently-named
        # axis would silently shard weights and reduce over different
        # axes
        raise ValueError('tp_axis must be "tp" (param_specs convention)')
    sp = mesh.shape[axis]
    unit = 2 * sp if layout == "zigzag" else sp
    assert tokens.shape[1] % unit == 0, (
        f"prompt length {tokens.shape[1]} not divisible by {unit}")
    if layout == "zigzag":
        perm, inv = zigzag_permutation(tokens.shape[1], sp)
        tokens = tokens[:, perm]
    tokens = jax.device_put(tokens, NamedSharding(mesh, P(None, axis)))
    logits_all, k_all, v_all = _sp_prefill_jit(params, tokens, cfg, mesh,
                                               axis, layout, tp_axis)
    if layout == "zigzag":
        # global last token lives in stripe 2sp-1 → device 0's last row
        if kv_order == "natural":
            return logits_all[0], k_all[:, :, inv], v_all[:, :, inv]
        return logits_all[0], k_all, v_all
    return logits_all[-1], k_all, v_all
