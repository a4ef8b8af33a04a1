"""The Nemotron-H family's forward pass, plain: float32, matmuls at
`highest`, no cache, no kernel, no chunking, one sequence at a time, the
weights read layer by layer from the run's own checkpoint. It imports
nothing of `dynamo_tpu`.

Hidden x, eps `layer_norm_epsilon`. Layer i is ONE mixer, of the kind
`hybrid_override_pattern[i]` names: x = x + mixer_i(rms(x) w_i).

    M  Mamba-2. [z | xBC | dt] = in_proj(u), widths inner | inner + 2 G N | H
       with inner = mamba_num_heads x mamba_head_dim (not expand x hidden).
       xBC_t = silu(bias + sum_{j<K} c_j xBC_{t-K+1+j}), depthwise, causal,
       zeros before the sequence; split x_t (H heads of P), B_t, C_t (G
       groups of N; head h reads group h // (H / G)).
       dt_t = softplus(dt_t + dt_bias) a head, A = -exp(A_log) a head.
       A head's state S (P x N), zero before the sequence, TOKEN BY TOKEN:
           S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t
           y_t = S_t C_t + D x_t
       g = y * silu(z); RMS norm of g over each of G groups of inner / G,
       times a weight of inner; out_proj.
    E  experts. s = sigmoid(u Wr^T) over all experts; chosen = the k
       largest of s + e_score_correction_bias; g_e = routed_scaling_factor
       s_e / (sum over the chosen of s + 1e-20), the UNBIASED scores;
       out = sum over the chosen of g_e Wdown_e relu(Wup_e u)^2
             + Vdown relu(Vup u)^2     (the shared expert, every token)
    *  attention. q, k, v = u Wq^T, u Wk^T, u Wv^T, no bias, NO rotation
       (the family has no position embedding), each q head attends the kv
       head of its group causally, scores q.k / sqrt(head_dim); Wo.

After the last layer rms(x) w_f and the untied lm_head; logits at position
t predict token t + 1.

The recurrence here is a `lax.scan` over tokens, the definition itself; the
program runs its chunked form in prefill and a fused step in decode, so the
two derivations check each other. The projections, the expert stacks, the
shared expert and the head are taken at the precision the configuration
states (`lib/refio.py:as_served`); embedding, norms, router and its bias,
convolution, A_log, D and dt_bias are untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib.refio import ROW_BUCKETS, TOKEN_BUCKETS, as_served, pad_to
from reference.llama import head, rms_norm


def sizes(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "m_heads": config["mamba_num_heads"],
            "m_dim": config["mamba_head_dim"],
            "groups": config["n_groups"], "state": config["ssm_state_size"],
            "k": config["num_experts_per_tok"],
            "scaling": float(config.get("routed_scaling_factor", 1.0)),
            "eps": float(config.get("layer_norm_epsilon", 1e-5))}


def mamba(u, w, *, m_heads, m_dim, groups, state, eps, **_):
    t = u.shape[0]
    inner, gn = m_heads * m_dim, groups * state
    proj = u @ w["mixer.in_proj"].T
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * gn],
                  proj[:, 2 * inner + 2 * gn:])
    taps = w["mixer.conv1d"][:, 0, :]                       # (C, K)
    kernel = taps.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((kernel - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = w["mixer.conv1d.bias"] + sum(
        taps[:, j] * padded[j:j + t] for j in range(kernel))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, m_heads, m_dim)
    b = jnp.repeat(xbc[:, inner:inner + gn].reshape(t, groups, state),
                   m_heads // groups, axis=1)               # (T, H, N)
    c = jnp.repeat(xbc[:, inner + gn:].reshape(t, groups, state),
                   m_heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w["mixer.dt_bias"])           # (T, H)
    a = -jnp.exp(w["mixer.A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((m_heads, m_dim, state)),
                        (x, b, c, dt))
    y = y + w["mixer.D"][:, None] * x
    g = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(t, inner) * w["mixer.norm"]) @ w["mixer.out_proj"].T


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def experts(u, w, *, k, scaling, **_):
    s = jax.nn.sigmoid(u @ w["mixer.gate"].T)
    _, chosen = jax.lax.top_k(s + w["mixer.gate.e_score_correction_bias"],
                              k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    gates = scaling * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                + 1e-20)

    def one(out, expert):
        e, up, down = expert
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        return out + gate[:, None] * (relu2(u @ up.T) @ down.T), None

    n = w["up_proj"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(n), w["up_proj"], w["down_proj"]))
    return out + relu2(u @ w["mixer.shared_experts.up_proj"].T) \
        @ w["mixer.shared_experts.down_proj"].T


def attention(u, w, *, heads, kv_heads, head_dim, **_):
    t = u.shape[0]
    q = (u @ w["mixer.q_proj"].T).reshape(t, heads, head_dim)
    k = (u @ w["mixer.k_proj"].T).reshape(t, kv_heads, head_dim)
    v = (u @ w["mixer.v_proj"].T).reshape(t, kv_heads, head_dim)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * head_dim)
    return out @ w["mixer.o_proj"].T


MIXERS = {"M": mamba, "E": experts, "*": attention}
SERVED = {"M": ("mixer.in_proj", "mixer.out_proj"),
          "*": ("mixer.q_proj", "mixer.k_proj", "mixer.v_proj",
                "mixer.o_proj"),
          "E": ("mixer.shared_experts.up_proj",
                "mixer.shared_experts.down_proj")}
AS_IS = {"M": ("mixer.conv1d.weight", "mixer.conv1d.bias", "mixer.dt_bias",
               "mixer.A_log", "mixer.D", "mixer.norm.weight"),
         "*": (),
         "E": ("mixer.gate.weight", "mixer.gate.e_score_correction_bias")}


@functools.partial(jax.jit, static_argnames=("kind", "sz"))
def layer(x, w, *, kind, sz):
    sz = dict(sz)
    return x + MIXERS[kind](rms_norm(x, w["norm"], sz["eps"]), w, **sz)


def layer_weights(read, i: int, kind: str, bits: int, n_experts: int
                  ) -> dict:
    p = f"backbone.layers.{i}."
    w = {"norm": read(p + "norm.weight")}
    for n in SERVED[kind]:
        w[n] = as_served(read(p + n + ".weight"), bits)
    for n in AS_IS[kind]:
        w[n[:-len(".weight")] if n.endswith(".weight") else n] = read(p + n)
    if kind == "E":
        for n in ("up_proj", "down_proj"):
            w[n] = jnp.stack([as_served(
                read(p + f"mixer.experts.{e}.{n}.weight"), bits)
                for e in range(n_experts)])
    return w


def logits(read, config: dict, sequences: list, starts: list, bits: dict
           ) -> list:
    """For each sequence of token ids, the float32 logits that predict its
    tokens from `start` on: rows start-1 .. len-2, as numpy (n, vocab).
    A sequence is padded to a bucket with id 0 AFTER its tokens: every
    mixer is causal, so no real row sees the padding."""
    with jax.default_matmul_precision("highest"):
        sz = tuple(sorted(sizes(config).items()))
        pattern = config["hybrid_override_pattern"][
            :config["num_hidden_layers"]]
        embed = read.numpy("backbone.embeddings.weight")
        xs = []
        for ids in sequences:
            padded = list(ids) + [0] * (pad_to(len(ids), TOKEN_BUCKETS)
                                        - len(ids))
            xs.append(jnp.asarray(embed[np.asarray(padded)])
                      .astype(jnp.float32))
        for i, kind in enumerate(pattern):
            w = layer_weights(read, i, kind, bits["layers"],
                              config["n_routed_experts"])
            jax.block_until_ready(xs)
            xs = [layer(x, w, kind=kind, sz=sz) for x in xs]
        norm_w = read("backbone.norm_f.weight")
        lm_head = as_served(read("lm_head.weight"), bits["lm_head"])
        eps = dict(sz)["eps"]
        out = []
        for x, ids, start in zip(xs, sequences, starts):
            n = len(ids) - start
            rows = jnp.arange(pad_to(n, ROW_BUCKETS)) + start - 1
            got = head(x[jnp.minimum(rows, len(ids) - 1)], norm_w, lm_head,
                       eps=eps)
            out.append(np.asarray(got[:n]))
        return out
