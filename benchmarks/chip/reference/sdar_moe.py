"""The SDAR-MoE family's forward pass and its generation by diffusion over
blocks, plain: float32, matmuls at `highest`, no cache, no kernel, no
routing tricks (every expert computed for every token and weighted by its
gate, zero where it was not chosen). It imports nothing of `dynamo_tpu`.

Block, hidden x, eps from the config:
    hn = rms(x) w_in ; q, k, v = hn Wq^T, hn Wk^T, hn Wv^T   (no biases)
    q = rms_head(q) w_qn ; k = rms_head(k) w_kn   (over each head's D, one
        weight vector a layer each), then RoPE (halves rotated), whole head
    scores q.k / sqrt(D); key j visible to query i iff j // B <= i // B
    x = x + concat Wo^T
    hn = rms(x) w_post ; p = softmax(hn Wr^T) over all experts
    S = the k largest ; g_e = p_e / sum over S of p       (norm_topk_prob)
    x = x + sum over S of g_e Wdown_e (silu(Wgate_e hn) * Wup_e hn)
    final norm, untied lm_head. Logits at position i predict the token AT
    position i (masked-token prediction, no shift).

Generation, block length B, T steps a block (`deployment.worker_flags`),
mask id M (`mask_token_id`): the block over [bB, (b+1)B) starts from the ids
known there (a prompt's tail) and M elsewhere. Each step runs the model over
the clean ids of all earlier blocks and the block's state and fixes B / T of
the masked positions: `sequential` the leftmost, `low_confidence_static`
those whose best token has the highest probability (leftmost of equals).
When nothing is masked the block's final ids are the context of later
blocks.

`logits` gives, for each served position, the logits of the step that fixed
it, teacher-forced with the served ids. The clean ids and every step's noisy
copy of every block go through ONE forward: a copy sits at its block's
positions, sees the clean keys of earlier blocks and its own B rows, and no
clean row sees a copy. That is the same mathematics as a forward a step.
Under `sequential` the served ids determine every state, so one pass does;
under `low_confidence_static` the choice of a step needs the step before,
so there are T passes, and a position past the served ones (a block that
`max_tokens` cut) takes the reference's own best token.

The projections, the experts and the head are taken at the precision the
configuration states (`lib/refio.py:as_served`); embedding, norms and the
router are untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib.refio import ROW_BUCKETS, TOKEN_BUCKETS, as_served, pad_to
from reference.llama import head, rms_norm, sizes

ATTENTION = ("self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
             "self_attn.o_proj")
EXPERT = ("gate_proj", "up_proj", "down_proj")


def rope_at(x, positions, theta):
    """x: (T, heads, D) at `positions` (T,); the two halves of D rotate."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def visible(block, copy):
    """(T, T) bool, query row by key column. `block`: a token's block
    index; `copy`: 0 for a clean token, c > 0 for a token of noisy copy c,
    -1 for padding. A clean key is seen by the clean queries of its block
    and later ones and by the copies of later blocks; a copy's key by its
    own copy. Padding keys are seen by nobody; padding queries see key 0,
    so that their softmax is finite."""
    qb, kb = block[:, None], block[None, :]
    qc, kc = copy[:, None], copy[None, :]
    see = jnp.where(kc == 0, jnp.where(qc == 0, kb <= qb, kb < qb),
                    (kc == qc) & (kc > 0))
    first = jnp.arange(block.shape[0])[None, :] == 0
    return jnp.where(qc < 0, first, see)


def attention(x, w, positions, see, heads, kv_heads, head_dim, theta, eps):
    t = x.shape[0]
    h = rms_norm(x, w["input_layernorm"], eps)
    q = (h @ w["self_attn.q_proj"].T).reshape(t, heads, head_dim)
    k = (h @ w["self_attn.k_proj"].T).reshape(t, kv_heads, head_dim)
    v = (h @ w["self_attn.v_proj"].T).reshape(t, kv_heads, head_dim)
    q = rope_at(rms_norm(q, w["self_attn.q_norm"], eps), positions, theta)
    k = rope_at(rms_norm(k, w["self_attn.k_norm"], eps), positions, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head_dim))
    probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * head_dim)
    return x + out @ w["self_attn.o_proj"].T


def experts(h, w, k):
    p = jax.nn.softmax(h @ w["router"].T, axis=-1)
    best, chosen = jax.lax.top_k(p, k)
    gates = best / jnp.sum(best, axis=-1, keepdims=True)

    def one(out, expert):
        e, w_gate, w_up, w_down = expert
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        y = (jax.nn.silu(h @ w_gate.T) * (h @ w_up.T)) @ w_down.T
        return out + gate[:, None] * y, None

    n = w["gate_proj"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(n), w["gate_proj"], w["up_proj"], w["down_proj"]))
    return out


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "k"))
def layer(x, w, positions, block, copy, *, heads, kv_heads, head_dim, theta,
          eps, k):
    x = attention(x, w, positions, visible(block, copy), heads, kv_heads,
                  head_dim, theta, eps)
    return x + experts(rms_norm(x, w["post_attention_layernorm"], eps), w, k)


def layer_weights(read, i: int, bits: int, n_experts: int) -> dict:
    p = f"model.layers.{i}."
    w = {n: as_served(read(p + n + ".weight"), bits) for n in ATTENTION}
    for n in ("input_layernorm", "post_attention_layernorm",
              "self_attn.q_norm", "self_attn.k_norm"):
        w[n] = read(p + n + ".weight")
    w["router"] = read(p + "mlp.gate.weight")
    for n in EXPERT:
        w[n] = jnp.stack([as_served(
            read(p + f"mlp.experts.{e}.{n}.weight"), bits)
            for e in range(n_experts)])
    return w


class Blocks:
    """The denoising of one sequence's served blocks, teacher-forced: for
    every block that holds a served position, which positions are known
    and with what id, step by step."""

    def __init__(self, ids: list, start: int, block: int, steps: int,
                 mask_id: int) -> None:
        self.ids, self.start, self.b = list(ids), start, block
        self.per, self.mask_id = block // steps, mask_id
        self.first = start // block
        n_blocks = (len(ids) - 1) // block + 1 - self.first
        # a block's state: position -> id, for the positions known
        self.known = [{p: self.ids[p] for p in range(
            (self.first + i) * block, min(start, (self.first + i + 1)
                                          * block))}
            for i in range(n_blocks)]
        self.whole = len(ids) // block * block      # clean ids: whole blocks

    def masked(self, i: int) -> list:
        lo = (self.first + i) * self.b
        return [p for p in range(lo, lo + self.b) if p not in self.known[i]]

    def copy_of(self, i: int) -> tuple[list, list]:
        """(ids, positions) of block i's state now."""
        lo = (self.first + i) * self.b
        pos = list(range(lo, lo + self.b))
        return [self.known[i].get(p, self.mask_id) for p in pos], pos

    def fix(self, i: int, chosen: list, own: dict) -> None:
        """Fix positions of block i: a served position takes the served id,
        one past them (`own`: position -> the reference's best) its own;
        without one it stays masked (`sequential` never needs it: what is
        served lies to its left)."""
        for p in chosen:
            if p < len(self.ids):
                self.known[i][p] = self.ids[p]
            elif p in own:
                self.known[i][p] = own[p]


def forward(read, config: dict, bits: dict, batches: list) -> list:
    """Each batch: (ids, positions, block, copy, rows), numpy vectors of
    one extended sequence and the rows whose logits are wanted. One sweep
    over the layers for all of them; returns [(len(rows), vocab) float32]."""
    sz = sizes(config)
    k, n_experts = config["num_experts_per_tok"], config["num_experts"]
    embed = read.numpy("model.embed_tokens.weight")
    xs, meta = [], []
    for ids, positions, block, copy, _ in batches:
        n = pad_to(len(ids), TOKEN_BUCKETS)

        def padded(a, fill):
            return jnp.asarray(np.concatenate(
                [a, np.full(n - len(a), fill)]).astype(np.int32))

        xs.append(jnp.asarray(embed[np.asarray(padded(ids, 0))])
                  .astype(jnp.float32))
        meta.append((padded(positions, 0), padded(block, 0),
                     padded(copy, -1)))
    for i in range(config["num_hidden_layers"]):
        w = layer_weights(read, i, bits["layers"], n_experts)
        jax.block_until_ready(xs)
        xs = [layer(x, w, *m, **sz, k=k) for x, m in zip(xs, meta)]
    norm_w = read("model.norm.weight")
    lm_head = as_served(read("lm_head.weight"), bits["lm_head"])
    out = []
    for x, (*_, rows) in zip(xs, batches):
        take = np.zeros(pad_to(max(len(rows), 1), ROW_BUCKETS), np.int32)
        take[:len(rows)] = rows
        got = head(x[jnp.asarray(take)], norm_w, lm_head, eps=sz["eps"])
        out.append(np.asarray(got[:len(rows)]))
    return out


def extended(seq: Blocks, copies: list) -> tuple:
    """The clean ids of a sequence's whole blocks followed by `copies`
    [(ids, positions)], as the vectors `forward` takes (rows left empty)."""
    ids = seq.ids[:seq.whole]
    positions = list(range(seq.whole))
    copy = [0] * seq.whole
    for c, (c_ids, c_pos) in enumerate(copies, start=1):
        ids, positions = ids + c_ids, positions + c_pos
        copy += [c] * len(c_ids)
    positions = np.asarray(positions)
    return (np.asarray(ids), positions, positions // seq.b,
            np.asarray(copy))


def logits(read, config: dict, sequences: list, starts: list, bits: dict
           ) -> list:
    """For each sequence of ids (prompt + served) the float32 logits of
    its served positions, rows start .. len-1, each from the step that
    fixed it: numpy (n, vocab)."""
    flags = config["deployment"]["worker_flags"]
    block = int(flags["dllm-block-length"])
    steps = int(flags.get("dllm-denoising-steps") or block)
    strategy = flags.get("dllm-unmasking-strategy", "sequential")
    mask_id = int(config["mask_token_id"])
    seqs = [Blocks(ids, start, block, steps, mask_id)
            for ids, start in zip(sequences, starts)]
    out = [np.zeros((len(s.ids) - s.start, config["vocab_size"]),
                    np.float32) for s in seqs]
    with jax.default_matmul_precision("highest"):
        # `sequential`: a step's choice is known beforehand, so every
        # step's copy of every block rides one pass. Otherwise a pass a
        # step.
        passes = 1 if strategy == "sequential" else steps
        for _ in range(passes):
            batches, wanted = [], []
            for s in seqs:
                copies, want = [], []      # want: (row, position, block i)
                for i in range(len(s.known)):
                    for _ in range(steps if passes == 1 else 1):
                        todo = s.masked(i)
                        if not todo or todo[0] >= len(s.ids):
                            break
                        c_ids, c_pos = s.copy_of(i)
                        base = s.whole + len(copies) * block
                        copies.append((c_ids, c_pos))
                        rows = [(base + p - c_pos[0], p, i) for p in todo]
                        if passes == 1:
                            rows = rows[:s.per]
                            s.fix(i, [p for _, p, _ in rows], {})
                        want += rows
                vec = extended(s, copies)
                batches.append((*vec, [r for r, _, _ in want]))
                wanted.append(want)
            for s, want, z, dst in zip(seqs, wanted,
                                       forward(read, config, bits, batches),
                                       out):
                fixed = [(j, p) for j, (_, p, _) in enumerate(want)] \
                    if passes == 1 else _most_confident(s, want, z)
                for j, p in fixed:
                    if s.start <= p < len(s.ids):
                        dst[p - s.start] = z[j]
    return out


def _most_confident(s: Blocks, want: list, z) -> list:
    """`low_confidence_static`: of each block's masked rows the `per` whose
    best token has the highest probability, the leftmost of equals. Fixes
    them; returns [(row of z, position)] of those."""
    shifted = z - z.max(axis=-1, keepdims=True)
    conf = -np.log(np.exp(shifted).sum(axis=-1))     # log p of the best
    fixed = []
    for i in range(len(s.known)):
        mine = sorted((-conf[j], p, j) for j, (_, p, b) in enumerate(want)
                      if b == i)[:s.per]
        s.fix(i, [p for _, p, _ in mine],
              {p: int(z[j].argmax()) for _, p, j in mine})
        fixed += [(j, p) for _, p, j in mine]
    return fixed
