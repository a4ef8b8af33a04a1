"""The LFM2-MoE family's forward pass, plain: float32, matmuls at `highest`,
no cache, no state, no kernel, no chunking, one sequence at a time, the
weights read layer by layer from the run's own checkpoint. It imports
nothing of `dynamo_tpu`.

Hidden x, eps `norm_eps`, rms(x) w = x / sqrt(mean(x^2) + eps) * w. Layer i:

    h = x + op_i(rms(x) w_op) ;  x = h + ffn_i(rms(h) w_ffn)

op, `layer_types[i]`:
    conv            [B | C | z] = in_proj(u), three chunks of hidden_size in
                    that order; g_t = B_t z_t; the depthwise causal sum over
                    the WHOLE sequence, zeros before its first token,
                        c_t = sum_{j<K} taps[:, j] g_{t-(K-1)+j}
                    (K = conv_L_cache, no bias, no activation);
                    out_proj(C_t c_t).
    full_attention  q, k, v = u Wq^T, u Wk^T, u Wv^T, no bias; RMS norm over
                    each head's 64 of q and of k with a weight, THEN RoPE
                    (halves rotated, base rope_theta, the whole head); each
                    q head attends the kv head of its group causally, scores
                    q.k / sqrt(head_dim); out_proj.
ffn:
    i < num_dense_layers   W2 (silu(W1 u) * W3 u)
    else                   s = sigmoid(u Wr^T), float32, all experts; chosen =
                           the k largest of s + expert_bias; w_e = s_e / (sum
                           over the chosen of s + 1e-6) x routed_scaling_factor,
                           the UNBIASED scores; out = sum over the chosen of
                           w_e W2_e (silu(W1_e u) * W3_e u), every expert in
                           turn by a loop, no shared expert.

After the last layer rms(x) w_f (`embedding_norm`) and the head, which is the
embedding (tied); logits at position t predict token t + 1.

The program keeps the convolution's last two inputs a sequence in a slot,
carries them over chunk boundaries and steps them a token at a time in
decode; here the sum runs over the sequence itself, so the two derivations
check each other. The projections, the dense and expert FFNs and the head
are taken at the precision the configuration states
(`lib/refio.py:as_served`); embedding, norms, router and its bias and the
taps are untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib.refio import ROW_BUCKETS, TOKEN_BUCKETS, as_served, pad_to
from reference.llama import head, rms_norm, rope

NORM_EPS = 1e-6             # on the sum of the chosen scores


def sizes(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return {"heads": heads,
            "kv_heads": config.get("num_key_value_heads", heads),
            "head_dim": config.get("head_dim")
            or config["hidden_size"] // heads,
            "theta": float(config.get("rope_theta", 1e6)),
            "k": config["num_experts_per_tok"],
            "scaling": float(config.get("routed_scaling_factor", 1.0)),
            "eps": float(config.get("norm_eps", 1e-5))}


def conv(u, w, **_):
    t = u.shape[0]
    b, c, z = jnp.split(u @ w["conv.in_proj"].T, 3, axis=-1)
    taps = w["conv.conv"][:, 0, :]                          # (hidden, K)
    kernel = taps.shape[1]
    g = jnp.concatenate([jnp.zeros((kernel - 1, b.shape[1]), b.dtype),
                         b * z])
    mixed = sum(taps[:, j] * g[j:j + t] for j in range(kernel))
    return (c * mixed) @ w["conv.out_proj"].T


def attention(u, w, *, heads, kv_heads, head_dim, theta, eps, **_):
    t = u.shape[0]
    q = (u @ w["self_attn.q_proj"].T).reshape(t, heads, head_dim)
    k = (u @ w["self_attn.k_proj"].T).reshape(t, kv_heads, head_dim)
    v = (u @ w["self_attn.v_proj"].T).reshape(t, kv_heads, head_dim)
    q = rope(rms_norm(q, w["self_attn.q_layernorm"], eps), theta)
    k = rope(rms_norm(k, w["self_attn.k_layernorm"], eps), theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * head_dim)
    return out @ w["self_attn.out_proj"].T


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1.T) * (u @ w3.T)) @ w2.T


def dense(u, w, **_):
    return swiglu(u, w["feed_forward.w1"], w["feed_forward.w3"],
                  w["feed_forward.w2"])


def experts(u, w, *, k, scaling, **_):
    s = jax.nn.sigmoid(u @ w["feed_forward.gate"].T)
    _, chosen = jax.lax.top_k(s + w["feed_forward.expert_bias"], k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    gates = scaling * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                + NORM_EPS)

    def one(out, expert):
        e, w1, w3, w2 = expert
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        return out + gate[:, None] * swiglu(u, w1, w3, w2), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(w["w1"].shape[0]), w["w1"], w["w3"], w["w2"]))
    return out


OPERATORS = {"conv": conv, "full_attention": attention}
FFNS = {"dense": dense, "moe": experts}
SERVED = {"conv": ("conv.in_proj", "conv.out_proj"),
          "full_attention": ("self_attn.q_proj", "self_attn.k_proj",
                             "self_attn.v_proj", "self_attn.out_proj"),
          "dense": ("feed_forward.w1", "feed_forward.w3",
                    "feed_forward.w2"),
          "moe": ()}
AS_IS = {"conv": ("conv.conv.weight",),
         "full_attention": ("self_attn.q_layernorm.weight",
                            "self_attn.k_layernorm.weight"),
         "dense": (),
         "moe": ("feed_forward.gate.weight", "feed_forward.expert_bias")}


@functools.partial(jax.jit, static_argnames=("op", "ffn", "sz"))
def layer(x, w, *, op, ffn, sz):
    sz = dict(sz)
    h = x + OPERATORS[op](rms_norm(x, w["operator_norm"], sz["eps"]), w,
                          **sz)
    return h + FFNS[ffn](rms_norm(h, w["ffn_norm"], sz["eps"]), w, **sz)


def layer_weights(read, i: int, op: str, ffn: str, bits: int,
                  n_experts: int) -> dict:
    p = f"model.layers.{i}."
    w = {n: read(p + n + ".weight") for n in ("operator_norm", "ffn_norm")}
    for kind in (op, ffn):
        for n in SERVED[kind]:
            w[n] = as_served(read(p + n + ".weight"), bits)
        for n in AS_IS[kind]:
            w[n[:-len(".weight")] if n.endswith(".weight") else n] = \
                read(p + n)
    if ffn == "moe":
        for n in ("w1", "w3", "w2"):
            w[n] = jnp.stack([as_served(
                read(p + f"feed_forward.experts.{e}.{n}.weight"), bits)
                for e in range(n_experts)])
    return w


def logits(read, config: dict, sequences: list, starts: list, bits: dict
           ) -> list:
    """For each sequence of token ids, the float32 logits that predict its
    tokens from `start` on: rows start-1 .. len-2, as numpy (n, vocab).
    A sequence is padded to a bucket with id 0 AFTER its tokens: every
    operator is causal, so no real row sees the padding."""
    with jax.default_matmul_precision("highest"):
        sz = tuple(sorted(sizes(config).items()))
        types = list(config["layer_types"])[:config["num_hidden_layers"]]
        embed = read.numpy("model.embed_tokens.weight")
        xs = []
        for ids in sequences:
            padded = list(ids) + [0] * (pad_to(len(ids), TOKEN_BUCKETS)
                                        - len(ids))
            xs.append(jnp.asarray(embed[np.asarray(padded)])
                      .astype(jnp.float32))
        for i, op in enumerate(types):
            ffn = "dense" if i < config["num_dense_layers"] else "moe"
            w = layer_weights(read, i, op, ffn, bits["layers"],
                              config["num_experts"])
            jax.block_until_ready(xs)
            xs = [layer(x, w, op=op, ffn=ffn, sz=sz) for x in xs]
        norm_w = read("model.embedding_norm.weight")
        lm_head = as_served(read("model.embed_tokens.weight"),
                            bits["lm_head"])
        eps = dict(sz)["eps"]
        out = []
        for x, ids, start in zip(xs, sequences, starts):
            n = len(ids) - start
            rows = jnp.arange(pad_to(n, ROW_BUCKETS)) + start - 1
            got = head(x[jnp.minimum(rows, len(ids) - 1)], norm_w, lm_head,
                       eps=eps)
            out.append(np.asarray(got[:n]))
        return out
