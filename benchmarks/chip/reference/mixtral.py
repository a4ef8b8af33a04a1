"""The Mixtral family's forward pass, plain: the llama family's attention
(`reference/llama.py`), and in place of its feed-forward block

    scores = h router^T                       (one score an expert)
    the k best experts of a token, their scores through a softmax
    out = sum over those of gate x (silu(h W1^T) * (h W3^T)) W2^T

float32, matmuls at `highest`, every expert computed for every token and
weighted by its gate (zero where it was not chosen): slow and plain. The
experts' three projections are taken at the precision the configuration
states, like the attention's; the router is untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from lib.refio import as_served
from reference import llama


def experts(h, w, k):
    scores = h @ w["router"].T
    best, chosen = jax.lax.top_k(scores, k)
    gates = jax.nn.softmax(best, axis=-1)
    out = jnp.zeros_like(h)
    for e in range(w["w1"].shape[0]):
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        y = (jax.nn.silu(h @ w["w1"][e].T) * (h @ w["w3"][e].T)) @ w["w2"][e].T
        out = out + gate[:, None] * y
    return out


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "k"))
def layer(x, w, *, heads, kv_heads, head_dim, theta, eps, k):
    x = llama.attention(x, w, heads, kv_heads, head_dim, theta, eps)
    h = llama.rms_norm(x, w["post_attention_layernorm"], eps)
    return x + experts(h, w, k)


def layer_weights(read, i: int, bits: int, n_experts: int) -> dict:
    p = f"model.layers.{i}."
    w = {n: as_served(read(p + n + ".weight"), bits)
         for n in llama.PROJECTIONS[:4]}
    for n in ("input_layernorm", "post_attention_layernorm"):
        w[n] = read(p + n + ".weight")
    moe = p + "block_sparse_moe."
    w["router"] = read(moe + "gate.weight")
    for n in ("w1", "w2", "w3"):
        w[n] = jnp.stack([as_served(
            read(moe + f"experts.{e}.{n}.weight"), bits)
            for e in range(n_experts)])
    return w


def logits(read, config: dict, sequences: list, starts: list, bits: dict
           ) -> list:
    k = int(config.get("num_experts_per_tok", 2))
    return llama.logits(
        read, config, sequences, starts, bits,
        layer_fn=functools.partial(layer, k=k),
        weights_fn=functools.partial(
            layer_weights, n_experts=config["num_local_experts"]))
