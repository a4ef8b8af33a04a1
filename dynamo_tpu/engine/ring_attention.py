"""Ring attention: sequence-parallel exact attention over a device ring.

Long-context prefill is where a single chip runs out of HBM first — the
reference punts long context to engine TP + KV offload (SURVEY §5.7 notes
SP/CP is absent upstream); on TPU we own the engine, so sequence
parallelism is native. The sequence axis is sharded over a mesh axis
("sp"): each device holds one Q/K/V chunk, K/V chunks rotate around the
ring via `lax.ppermute` (one ICI hop per step — neighbor exchanges ride
the torus at full bisection bandwidth), and attention accumulates with the
flash-attention online-softmax recurrence, so the full (T, T) score matrix
never materializes on any one chip.

All collectives are XLA-inserted (`shard_map` + ppermute) per the
scaling-book recipe; block compute is plain dot-products the MXU tiles.

Two sequence layouts:
- "contiguous": device i holds chunk i. Causality at block granularity —
  every step computes the full (Tq, Tk) einsum and masks; fully-masked
  blocks burn FLOPs (late devices are all-live, early ones mostly dead,
  but SPMD steps are uniform so everyone pays the worst case).
- "zigzag": device i holds blocks (i, 2sp-1-i) of 2sp stripes. For every
  non-diagonal (holder, source) pair EXACTLY half the sub-block pairs are
  live and fully-unmasked: src < idx ⇒ both local q-halves attend the
  source's LOW kv stripe; src > idx ⇒ the local HIGH q-half attends both
  source stripes. Equal FLOPs per device per step (balanced ring), ~2×
  less attend work than masked-full computes, selected per device by a
  runtime `lax.cond` (legal inside shard_map — the predicate is the
  device's own scalar). Only the s=0 diagonal step runs the full masked
  einsum.

Parity note: computes the same math as `attention.py`'s full prefill
attention — tested for equivalence on an 8-way CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_attend(q5, k, v, q_pos, kv_pos, causal: bool):
    """Partial attention of one Q chunk against one K/V chunk.

    q5: (B, Tq, KVH, G, D) — query heads grouped by kv head, so GQA K/V
    are NEVER materialized to full head count (`jnp.repeat` inside the
    ring body would copy the K/V chunk groups× on every ring step).
    k/v: (B, Tk, KVH, D). Returns (o_part (B, Tq, KVH, G, D) f32,
    m_part, l_part (B, KVH, G, Tq) f32) — unnormalized output + stats."""
    d = q5.shape[-1]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / jnp.sqrt(jnp.float32(d)))
    if causal:
        mask = kv_pos[None, :] <= q_pos[:, None]          # (Tq, Tk)
        scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    m_part = jnp.max(scores, axis=-1)                      # (B, KVH, G, Tq)
    p = jnp.exp(scores - m_part[..., None])
    l_part = jnp.sum(p, axis=-1)
    o_part = jnp.einsum("bhgqk,bkhd->bqhgd", p,
                        v.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    return o_part, m_part, l_part


def _merge(m, l, acc, o_p, m_p, l_p):
    """Online-softmax merge of one partial block into the running
    (max, denom, accumulator) — THE numerics-critical recurrence, shared
    by both ring layouts so they can never diverge. Stats are
    (B, KVH, G, Tq); acc/o_p are (B, Tq, KVH, G, D)."""
    m_new = jnp.maximum(m, m_p)
    scale_old = jnp.exp(m - m_new)
    scale_new = jnp.exp(m_p - m_new)
    acc = (acc * scale_old.transpose(0, 3, 1, 2)[..., None]
           + o_p * scale_new.transpose(0, 3, 1, 2)[..., None])
    return m_new, l * scale_old + l_p * scale_new, acc


def zigzag_permutation(t: int, sp: int):
    """(perm, inv) host-side index arrays: ``x[perm]`` reorders a length-t
    sequence into zigzag device order (device i gets stripes i and
    2sp-1-i back to back); ``y[inv]`` undoes it. t % (2*sp) == 0."""
    import numpy as np

    tb = t // (2 * sp)
    perm = np.concatenate([
        np.concatenate([np.arange(i * tb, (i + 1) * tb),
                        np.arange((2 * sp - 1 - i) * tb,
                                  (2 * sp - i) * tb)])
        for i in range(sp)])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(t)
    return perm, inv


def zigzag_positions(dev, tq: int, sp: int):
    """Global positions of device `dev`'s local rows under the zigzag
    layout (traced-friendly: dev may be a traced axis_index)."""
    tb = tq // 2
    r = jnp.arange(tb)
    return jnp.concatenate([dev * tb + r, (2 * sp - 1 - dev) * tb + r])


def _ring_zigzag_local(q, k, v, axis_name: str):
    """Causal ring attention under the zigzag layout (per-shard body).

    Local rows are [stripe idx ; stripe 2sp-1-idx]. Non-diagonal steps
    compute exactly half the sub-blocks, fully unmasked (see module
    docstring); the diagonal step masks exactly."""
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    kvh = k.shape[2]
    groups = h // kvh
    tb = tq // 2
    q5 = q.astype(jnp.float32).reshape(b, tq, kvh, groups, d)
    q_pos = zigzag_positions(idx, tq, sp)

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(s, carry):
        k_cur, v_cur, m, l, acc = carry
        src = (idx - s) % sp
        kf = k_cur.astype(jnp.float32)

        def diagonal(_):
            kv_pos = zigzag_positions(src, tk, sp)
            return _block_attend(q5, kf, v_cur, q_pos, kv_pos, True)

        def low_half(_):
            # src < idx: both q-halves vs the source's LOW stripe, no mask
            o_p, m_p, l_p = _block_attend(
                q5, kf[:, :tb], v_cur[:, :tb],
                q_pos, jnp.arange(tb), False)
            return o_p, m_p, l_p

        def high_half(_):
            # src > idx: HIGH q-half vs both source stripes, no mask
            o_p, m_p, l_p = _block_attend(
                q5[:, tb:], kf, v_cur,
                q_pos[tb:], jnp.arange(tk), False)
            pad_o = jnp.zeros((b, tb, kvh, groups, d), jnp.float32)
            pad_m = jnp.full((b, kvh, groups, tb), _NEG_INF, jnp.float32)
            pad_l = jnp.zeros((b, kvh, groups, tb), jnp.float32)
            return (jnp.concatenate([pad_o, o_p], axis=1),
                    jnp.concatenate([pad_m, m_p], axis=-1),
                    jnp.concatenate([pad_l, l_p], axis=-1))

        o_p, m_p, l_p = lax.cond(
            s == 0, diagonal,
            lambda _: lax.cond(src < idx, low_half, high_half, None),
            None)
        m, l, acc = _merge(m, l, acc, o_p, m_p, l_p)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m, l, acc

    m0, l0, acc0 = _init_carry(q5)
    _, _, _, l, acc = lax.fori_loop(0, sp, body, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, tq, h, d).astype(q.dtype)


def _init_carry(q5):
    """Online-softmax init (max, denom, acc) DERIVED from q5 so the
    arrays inherit q5's varying-axes set — a plain jnp.zeros carry is
    'unvarying' and shard_map's fori_loop typing rejects it; deriving
    works for 1-D rings and 2-D (sp, tp) meshes alike."""
    zero_stat = (q5[..., 0] * 0.0).transpose(0, 2, 3, 1)  # (B,KVH,G,Tq)
    return zero_stat + _NEG_INF, zero_stat, q5 * 0.0


def ring_attention_local(q, k, v, axis_name: str, causal: bool = True,
                         layout: str = "contiguous"):
    """The per-shard body: call INSIDE `shard_map` over ``axis_name``.

    q: (B, Tq, H, D) local chunk; k/v: (B, Tk, KVH, D) local chunk.
    Tq/Tk are the per-device chunk lengths; global positions are derived
    from the axis index so the causal mask is exact across chunks.
    layout="zigzag" (causal only) balances causal work across the ring —
    the caller must hand each device its two zigzag stripes
    (`zigzag_permutation`)."""
    if layout == "zigzag":
        assert causal, "zigzag layout is a causal-balancing scheme"
        return _ring_zigzag_local(q, k, v, axis_name)
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    kvh = k.shape[2]
    groups = h // kvh
    q5 = q.astype(jnp.float32).reshape(b, tq, kvh, groups, d)
    q_pos = idx * tq + jnp.arange(tq)

    perm = [(i, (i + 1) % sp) for i in range(sp)]  # receive neighbor's kv

    def body(s, carry):
        k_cur, v_cur, m, l, acc = carry
        src = (idx - s) % sp                       # whose chunk we hold
        kv_pos = src * tk + jnp.arange(tk)
        o_p, m_p, l_p = _block_attend(q5, k_cur.astype(jnp.float32),
                                      v_cur, q_pos, kv_pos, causal)
        m, l, acc = _merge(m, l, acc, o_p, m_p, l_p)
        # rotate K/V one hop around the ring (ICI neighbor exchange);
        # XLA overlaps the permute with the next block's compute
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m, l, acc

    # init derived from q5 so the carry's varying-axes typing matches the
    # loop outputs on any mesh (see _init_carry)
    m0, l0, acc0 = _init_carry(q5)
    _, _, _, l, acc = lax.fori_loop(0, sp, body, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, tq, h, d).astype(q.dtype)


def sp_mesh(sp: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    assert len(devices) >= sp, f"need {sp} devices, have {len(devices)}"
    import numpy as np

    return Mesh(np.asarray(devices[:sp]), axis_names=("sp",))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "causal", "axis", "layout"))
def _ring_attention_jit(q, k, v, mesh: Mesh, causal: bool, axis: str,
                        layout: str = "contiguous"):
    seq_spec = P(None, axis, None, None)
    fn = shard_map(
        functools.partial(ring_attention_local, axis_name=axis,
                          causal=causal, layout=layout),
        mesh=mesh, in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec)
    return fn(q, k, v)


def ring_attention(q, k, v, mesh: Mesh, causal: bool = True,
                   axis: str = "sp", layout: str = "contiguous"):
    """Global entry: q (B, T, H, D), k/v (B, T, KVH, D) with T divisible
    by the ``axis`` size (2× that for zigzag). Shards the sequence, runs
    the ring, returns the globally-correct attention output sharded the
    same way (zigzag permutation applied and undone internally)."""
    sp = mesh.shape[axis]
    unit = 2 * sp if layout == "zigzag" else sp
    assert q.shape[1] % unit == 0, (
        f"sequence {q.shape[1]} not divisible by {unit}")
    if layout == "zigzag":
        perm, inv = zigzag_permutation(q.shape[1], sp)
        q, k, v = q[:, perm], k[:, perm], v[:, perm]
    sharding = NamedSharding(mesh, P(None, axis, None, None))
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    out = _ring_attention_jit(q, k, v, mesh, causal, axis, layout)
    return out[:, inv] if layout == "zigzag" else out
