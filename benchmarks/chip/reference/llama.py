"""The llama family's forward pass, plain: Llama, Mistral and Qwen2 as their
papers and public modelling code describe them. float32 throughout, matmuls
at `highest`, full causal attention over the whole sequence: no cache, no
kernel, no batching, one sequence at a time, the weights read layer by layer
from the run's own checkpoint so that 7B fits.

    x = embed[tokens]
    each layer:  h = rms_norm(x) ; q, k, v = h Wq^T (+bq), h Wk^T (+bk), h Wv^T (+bv)
                 q, k = rope(q), rope(k)      (halves rotated, base rope_theta)
                 each q head attends the kv head of its group (heads / kv_heads to a group)
                 x = x + attn Wo^T
                 h = rms_norm(x) ; x = x + (silu(h Wg^T) * (h Wu^T)) Wd^T
    logits = rms_norm(x) lm_head^T

Biases are used where the checkpoint has them (Qwen2). The projections and
the head are taken at the precision the configuration states
(`lib/refio.py:as_served`); embedding, norms and biases are untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib.refio import ROW_BUCKETS, TOKEN_BUCKETS, as_served, pad_to

PROJECTIONS = ("self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
               "self_attn.o_proj", "mlp.gate_proj", "mlp.up_proj",
               "mlp.down_proj")


def sizes(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return {"heads": heads,
            "kv_heads": config.get("num_key_value_heads", heads),
            "head_dim": config.get("head_dim")
            or config["hidden_size"] // heads,
            "theta": float(config.get("rope_theta", 10000.0)),
            "eps": float(config.get("rms_norm_eps", 1e-5))}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, theta):
    """x: (T, heads, D) at positions 0..T-1; the two halves of D rotate."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(x, w, heads, kv_heads, head_dim, theta, eps):
    t = x.shape[0]
    h = rms_norm(x, w["input_layernorm"], eps)
    q = h @ w["self_attn.q_proj"].T + w.get("self_attn.q_proj.bias", 0.0)
    k = h @ w["self_attn.k_proj"].T + w.get("self_attn.k_proj.bias", 0.0)
    v = h @ w["self_attn.v_proj"].T + w.get("self_attn.v_proj.bias", 0.0)
    q = rope(q.reshape(t, heads, head_dim), theta)
    k = rope(k.reshape(t, kv_heads, head_dim), theta)
    v = v.reshape(t, kv_heads, head_dim)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * head_dim)
    return x + out @ w["self_attn.o_proj"].T


def swiglu(h, w):
    return (jax.nn.silu(h @ w["mlp.gate_proj"].T)
            * (h @ w["mlp.up_proj"].T)) @ w["mlp.down_proj"].T


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps"))
def layer(x, w, *, heads, kv_heads, head_dim, theta, eps):
    x = attention(x, w, heads, kv_heads, head_dim, theta, eps)
    return x + swiglu(rms_norm(x, w["post_attention_layernorm"], eps), w)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, norm_w, lm_head, *, eps):
    return rms_norm(x, norm_w, eps) @ lm_head.T


def layer_weights(read, i: int, bits: int) -> dict:
    p = f"model.layers.{i}."
    w = {n: as_served(read(p + n + ".weight"), bits) for n in PROJECTIONS}
    for n in PROJECTIONS[:3]:
        if p + n + ".bias" in read:
            w[n + ".bias"] = read(p + n + ".bias")
    for n in ("input_layernorm", "post_attention_layernorm"):
        w[n] = read(p + n + ".weight")
    return w


def logits(read, config: dict, sequences: list, starts: list, bits: dict,
           layer_fn=layer, weights_fn=layer_weights) -> list:
    """For each sequence of token ids, the float32 logits that predict its
    tokens from `start` on: rows start-1 .. len-2, as numpy (n, vocab).
    `read(name)` gives a tensor of the checkpoint as float32; `bits` is the
    precision of the layers' projections and of the head."""
    with jax.default_matmul_precision("highest"):
        sz = sizes(config)
        embed = read.numpy("model.embed_tokens.weight")
        xs = []
        for ids in sequences:
            padded = list(ids) + [0] * (pad_to(len(ids), TOKEN_BUCKETS)
                                        - len(ids))
            xs.append(jnp.asarray(embed[np.asarray(padded)])
                      .astype(jnp.float32))
        for i in range(config["num_hidden_layers"]):
            # this layer's weights are read while the last one computes,
            # and no further ahead: two layers of weights on the device
            w = weights_fn(read, i, bits["layers"])
            jax.block_until_ready(xs)
            xs = [layer_fn(x, w, **sz) for x in xs]
        norm_w = read("model.norm.weight")
        name = ("model.embed_tokens.weight"
                if config.get("tie_word_embeddings") else "lm_head.weight")
        lm_head = as_served(read(name), bits["lm_head"])
        out = []
        for x, ids, start in zip(xs, sequences, starts):
            n = len(ids) - start
            rows = jnp.arange(pad_to(n, ROW_BUCKETS)) + start - 1
            got = head(x[jnp.minimum(rows, len(ids) - 1)], norm_w, lm_head,
                       eps=sz["eps"])
            out.append(np.asarray(got[:n]))
        return out
