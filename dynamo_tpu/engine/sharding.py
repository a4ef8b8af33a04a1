"""Mesh + sharding layout for the serving engine.

The scaling-book recipe: pick a mesh, annotate shardings on params/cache,
let XLA insert the collectives. Axes:
- "dp": replica axis — engine-level data parallelism (each dp slice is an
  independently-addressable worker rank, the reference's dp_rank routing,
  SURVEY.md §2.10)
- "tp": tensor parallelism — attention heads / ffn hidden sharded; XLA
  inserts the all-reduce after o-proj and down-proj (megatron pattern)

Params layout (models/llama.py init_params):
  wq/wk/wv:   (L, E, Heads*D)  → shard out dim over tp
  wo:         (L, H*D, E)      → shard in dim over tp  (psum after)
  w_gate/up:  (L, E, F)        → shard F over tp
  w_down:     (L, F, E)        → shard F over tp       (psum after)
  embed:      (V, E)           → shard V over tp (gathered on lookup)
  lm_head:    (E, V)           → shard V over tp
KV cache (L, KVH, N, P, D)     → shard KVH over tp
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(dp: int = 1, tp: int = 1,
              devices: Optional[list] = None) -> Mesh:
    """(dp, tp) device mesh. A structured error (not an assert, which
    vanishes under `python -O`) names the requested factorization vs
    the backend's reality — a pod-slice misconfig must fail loudly at
    startup, not as a mystery reshape deep in Mesh()."""
    devices = devices if devices is not None else jax.devices()
    n = dp * tp
    if dp < 1 or tp < 1:
        raise ValueError(
            f"make_mesh: axis sizes must be >= 1, got dp={dp} tp={tp}")
    if len(devices) < n:
        platforms = sorted({str(getattr(d, "platform", "?"))
                            for d in devices}) or ["none"]
        raise ValueError(
            f"make_mesh: dp={dp} x tp={tp} needs {n} device(s) but the "
            f"backend has {len(devices)} "
            f"({', '.join(platforms)}) — shrink dp/tp or run on a "
            f"larger slice (XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=N emulates N devices on CPU)")
    arr = np.asarray(devices[:n]).reshape(dp, tp)
    return Mesh(arr, axis_names=("dp", "tp"))


def param_specs(attention_bias: bool = False,
                moe: bool = False, moe_tp: bool = False,
                qk_norm: bool = False) -> dict:
    """PartitionSpecs matching init_params' pytree structure.
    `attention_bias` (Qwen2 family) adds bq/bk/bv rows — biases shard
    like their weight's OUTPUT dim (megatron column-parallel).

    `moe` (Mixtral family) returns the EXPERT-PARALLEL serving layout
    instead: the (L, X, ...) expert stacks shard over "ep" on the
    expert axis; moe_mlp's dense-dispatch einsums contract over X, so
    GSPMD computes each chip's experts locally and inserts ONE psum
    for the weighted combine — the serving analog of ep_param_specs
    (mixtral.py), reusable under the engine's ordinary jit (no
    shard_map). With `moe_tp` (a 2-D ("ep","tp") mesh — the
    Mixtral-8x7B multi-host shape) attention/embeddings additionally
    shard megatron-style over "tp" while the router stays replicated;
    otherwise everything non-expert replicates."""
    if qk_norm:
        # (L, D) per-head norm weights: every shard needs all of D
        out = param_specs(attention_bias, moe, moe_tp)
        out["layers"].update({"q_norm": P(None, None),
                              "k_norm": P(None, None)})
        return out
    if moe:
        if moe_tp:
            base = param_specs(attention_bias)
            layers = dict(base["layers"])
            for k in ("w_gate", "w_up", "w_down"):
                layers.pop(k)
            out = {"embed": base["embed"], "layers": layers,
                   "final_norm": base["final_norm"],
                   "lm_head": base["lm_head"]}
        else:
            layers = {
                "attn_norm": P(None, None),
                "wq": P(None, None, None),
                "wk": P(None, None, None),
                "wv": P(None, None, None),
                "wo": P(None, None, None),
                "mlp_norm": P(None, None),
            }
            out = {
                "embed": P(None, None),
                "layers": layers,
                "final_norm": P(None),
                "lm_head": P(None, None),
            }
        out["layers"].update({
            "router": P(None, None, None),
            "w_gate": P(None, "ep", None, None),
            "w_up": P(None, "ep", None, None),
            "w_down": P(None, "ep", None, None),
        })
        return out
    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "mlp_norm": P(None, None),
        "w_gate": P(None, None, "tp"),
        "w_up": P(None, None, "tp"),
        "w_down": P(None, "tp", None),
    }
    if attention_bias:
        layers.update({"bq": P(None, "tp"), "bk": P(None, "tp"),
                       "bv": P(None, "tp")})
    return {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


def specs_for(params: dict, mesh: Optional[Mesh] = None) -> dict:
    """param_specs pruned/extended to match THIS param tree's layer
    keys (the bias rows exist only for attention_bias configs, the
    router/expert rows only for MoE; a tree.map over mismatched dicts
    raises). The mesh decides whether MoE attention tp-shards (2-D
    ("ep","tp")) or replicates (1-D ("ep",))."""
    specs = param_specs(
        attention_bias="bq" in params["layers"],
        moe="router" in params["layers"],
        moe_tp=mesh is not None and "tp" in mesh.axis_names,
        qk_norm="q_norm" in params["layers"])
    specs["layers"] = {k: specs["layers"][k] for k in params["layers"]}
    return specs


def cache_spec(mesh: Optional[Mesh] = None) -> P:
    # per-layer (KVH, N, P, D): kv heads over tp; fully replicated on
    # meshes without a "tp" axis (the ep serving mesh — every chip
    # runs full attention, only the expert FFN splits)
    if mesh is not None and "tp" not in mesh.axis_names:
        return P(None, None, None, None)
    return P("tp", None, None, None)


def param_sharding(mesh: Mesh, attention_bias: bool = False,
                   moe: bool = False, qk_norm: bool = False) -> dict:
    """NamedSharding tree matching init_params' structure."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_specs(attention_bias, moe=moe,
                    moe_tp=moe and "tp" in mesh.axis_names,
                    qk_norm=qk_norm),
        is_leaf=lambda x: isinstance(x, P))


def cache_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, cache_spec(mesh))


def shard_params(params: dict, mesh: Mesh) -> dict:
    from dynamo_tpu.engine.quant import QTensor, scale_spec

    specs = specs_for(params, mesh)

    def place(x, s):
        if isinstance(x, QTensor):
            # weight shards like its bf16 twin; the (*1s, N) scale can only
            # shard its last (output) dim
            return QTensor(
                q=jax.device_put(x.q, NamedSharding(mesh, s)),
                s=jax.device_put(
                    x.s, NamedSharding(mesh, scale_spec(s, x.s.ndim))),
                bits=x.bits, act_bits=x.act_bits)
        return jax.device_put(x, NamedSharding(mesh, s))

    return jax.tree.map(
        place, params, specs,
        is_leaf=lambda x: not isinstance(x, dict))


def shard_cache(cache, mesh: Mesh):
    ns = NamedSharding(mesh, cache_spec(mesh))
    return jax.tree.map(lambda x: jax.device_put(x, ns), cache)
