"""Nemotron-H family (`model_type: nemotron_h`): a hybrid stack whose layers
are ONE mixer each, of three kinds, named by `hybrid_override_pattern`:

    M  Mamba-2        x + out_proj(norm_g(ssm(conv(in_proj(rms(x)))) * silu(z)))
    E  experts        x + sum_{e in top-k} g_e W2_e relu(W1_e u)^2 + shared(u)
    *  attention      x + Wo attn(q, k, v)          (GQA, no position rotation)

An attention layer has no FFN and an expert layer no attention, so the
two-halves block of models/llama.py does not express it. Parameters are
stacked BY KIND (`layers.mamba`, `layers.moe`, `layers.attn`) and a static
table (`NemotronHConfig.table`) sends layer l to (kind, index within its
kind, index of its cache pair). The layer loop is Python over that table,
kernels inline under the jitted entry (no frame between: PERF.md §6, PR 35).

State. A Mamba-2 layer keeps, a SEQUENCE, a (heads, head_dim, state) float32
SSM state and the last `conv_kernel - 1` inputs of its convolution. Neither
grows with the context, so neither is paged: a sequence owns a SLOT
(engine/pages.py `SlotPool`) from admission to its end, and the entries take
`slots` beside `page_tables`. Slot 0 is scratch, as page 0 is: invalid lanes
and padding rows point at it and are masked (dt = 0, no input), so it stays
zero. The arrays ride in the cache tuples the entries donate, one pair a
layer that has state, in layer order: (convolution tail, SSM state) for a
Mamba layer, (K pages, V pages) for an attention layer, nothing for an
expert layer.

The recurrence, a head (state S, P x N): S_t = exp(dt_t A) S_{t-1} +
dt_t x_t (outer) B_t ; y_t = S_t C_t + D x_t. Prefill runs its chunked form
(`ssm_chunk_scan`, plain XLA einsums), starting from the slot's state where
`cached_lens > 0` and from zero where it is 0; decode runs one step a lane
in place at the lane's slot (`ssm_decode_update`, a Pallas kernel on the
TPU, its XLA twin elsewhere).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.attention import (paged_attention_decode,
                                         paged_attention_prefill, use_pallas)
from dynamo_tpu.engine.pages import kv_layer_shape, state_shapes
from dynamo_tpu.engine.quant import qm
from dynamo_tpu.models.llama import (_chunk_kv, _decode_kv, _write_kv,
                                     rms_norm)
from dynamo_tpu.models.mixtral import MoeConfig, moe_mlp

_HIGHEST = jax.lax.Precision.HIGHEST
# what one phase of the state update's queue moves; the queue is two such
# buffers, and the limit leaves the batch's small operands their room
_SSM_PHASE_BYTES = 8 << 20
_SSM_VMEM_BYTES = 4 * _SSM_PHASE_BYTES
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(MoeConfig):
    """Model facts of a `nemotron_h` config.json. `intermediate_size` is
    the routed experts' width; `num_layers` the length of `pattern`."""
    pattern: str = "MEM*E"
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    router_scoring: str = "sigmoid"
    routed_scaling: float = 2.5
    expert_act: str = "relu2"

    # the module whose entries serve this configuration
    # (models/__init__.py `family_module`); a per-sequence state beside the
    # pages
    entries_module = "dynamo_tpu.models.nemotron_h"
    recurrent = True

    def __post_init__(self):
        if len(self.pattern) != self.num_layers \
                or set(self.pattern) - set(KINDS):
            raise ValueError(
                f"pattern {self.pattern!r} must name {self.num_layers} "
                f"layers out of {sorted(KINDS)}")
        if self.mamba_heads % self.ssm_groups:
            raise ValueError("mamba_heads must be a multiple of ssm_groups")

    @property
    def table(self) -> tuple:
        """layer -> (kind, index within its kind, index of its cache pair
        or -1): D5's per-layer table."""
        seen = {k: 0 for k in KINDS.values()}
        out, cache = [], 0
        for ch in self.pattern:
            kind = KINDS[ch]
            has_state = kind != "moe"
            out.append((kind, seen[kind], cache if has_state else -1))
            seen[kind] += 1
            cache += has_state
        return tuple(out)

    def count(self, kind: str) -> int:
        return sum(1 for k, _, _ in self.table if k == kind)

    @property
    def num_moe_layers(self) -> int:
        return self.count("moe")

    @property
    def state_layers(self) -> int:
        return self.count("mamba")

    @property
    def slot_state(self) -> tuple:
        """What a slot holds a Mamba-2 layer (engine/pages.py
        `state_shapes`): the convolution's last K - 1 inputs in the
        activations' dtype, the SSM state in float32."""
        return (((self.conv_kernel - 1, self.conv_dim), None),
                ((self.mamba_heads, self.mamba_head_dim, self.ssm_state),
                 jnp.float32))

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=48,
                        num_layers=5, pattern="MEM*E", num_heads=4,
                        num_kv_heads=2, head_dim=16, page_size=4,
                        max_pages_per_seq=16, num_experts=8,
                        experts_per_token=2, shared_expert_size=80,
                        mamba_heads=4, mamba_head_dim=16, ssm_groups=2,
                        ssm_state=16, chunk_size=8)
        defaults.update(kw)
        if "pattern" in kw and "num_layers" not in kw:
            defaults["num_layers"] = len(kw["pattern"])
        return cls(**defaults)


def config_from_hf(hf: dict, **overrides) -> NemotronHConfig:
    """NemotronHConfig from a checkpoint's config.json keys."""
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("nemotron_h: grouped expert choice (n_group / "
                         "topk_group above 1) is not served")
    if hf.get("mlp_hidden_act", "relu2") != "relu2" \
            or hf.get("n_shared_experts", 1) != 1:
        raise ValueError("nemotron_h: only relu2 experts beside one shared "
                         "expert are served")
    pattern = hf["hybrid_override_pattern"][:hf["num_hidden_layers"]]
    cfg = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=int(hf["moe_intermediate_size"]),
        num_layers=len(pattern), pattern=pattern,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        rms_eps=float(hf.get("layer_norm_epsilon",
                             hf.get("norm_eps", 1e-5))),
        num_experts=int(hf["n_routed_experts"]),
        experts_per_token=int(hf["num_experts_per_tok"]),
        routed_scaling=float(hf.get("routed_scaling_factor", 1.0)),
        shared_expert_size=int(hf["moe_shared_expert_intermediate_size"]),
        mamba_heads=int(hf["mamba_num_heads"]),
        mamba_head_dim=int(hf["mamba_head_dim"]),
        ssm_groups=int(hf["n_groups"]), ssm_state=int(hf["ssm_state_size"]),
        conv_kernel=int(hf["conv_kernel"]),
        chunk_size=int(hf.get("chunk_size", 128)))
    cfg.update(overrides)
    return NemotronHConfig(**cfg)


# ---------------------------------------------------------------------------
# Parameters and state
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: NemotronHConfig) -> dict:
    """Random-init params, stacked by kind. `A_log` spreads over decades
    and `dt_bias` is negative for half the heads, so some heads of every
    layer remember hundreds of tokens: a dropped state shows."""
    E, F, X = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    Fs, H, KVH, D = (cfg.shared_expert_size, cfg.num_heads,
                     cfg.num_kv_heads, cfg.head_dim)
    Lm, Le, La = cfg.count("mamba"), cfg.count("moe"), cfg.count("attn")
    Hm, di, C, K = (cfg.mamba_heads, cfg.d_inner, cfg.conv_dim,
                    cfg.conv_kernel)
    keys = iter(jax.random.split(rng, 24))

    def dense(fan_in, *shape, dtype=None):
        w = jax.random.normal(next(keys), shape, jnp.float32) \
            / math.sqrt(fan_in)
        return w.astype(dtype or cfg.dtype)

    def norm(*shape):
        return jnp.ones(shape, jnp.float32)

    def uneven(*shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": dense(E, cfg.vocab_size, E),
        "layers": {
            "mamba": {
                "norm": norm(Lm, E),
                "in_proj": dense(E, Lm, E, di + C + Hm),
                "conv_w": dense(K, Lm, K, C, dtype=jnp.float32),
                "conv_b": dense(4, Lm, C, dtype=jnp.float32),
                "dt_bias": 2.0 * jax.random.normal(
                    next(keys), (Lm, Hm), jnp.float32) - 1.0,
                "A_log": 2.0 * jax.random.normal(
                    next(keys), (Lm, Hm), jnp.float32),
                "D": uneven(Lm, Hm),
                "gnorm": uneven(Lm, di),
                "out_proj": dense(di, Lm, di, E),
            },
            "moe": {
                "norm": norm(Le, E),
                "router": 4.0 * dense(E, Le, E, X, dtype=jnp.float32),
                "router_bias": 0.2 * jax.random.normal(
                    next(keys), (Le, X), jnp.float32),
                "w_up": dense(E, Le, X, E, F),
                "w_down": dense(F, Le, X, F, E),
                "w_shared_up": dense(E, Le, E, Fs),
                "w_shared_down": dense(Fs, Le, Fs, E),
            },
            "attn": {
                "norm": norm(La, E),
                "wq": dense(E, La, E, H * D),
                "wk": dense(E, La, E, KVH * D),
                "wv": dense(E, La, E, KVH * D),
                "wo": dense(H * D, La, H * D, E),
            },
        },
        "final_norm": norm(E),
        "lm_head": dense(E, E, cfg.vocab_size),
    }


def pad_expert_width(layers: dict, multiple: int = 128) -> dict:
    """The expert stacks with their width F padded up to a multiple of
    `multiple` with zeros: columns of w_up (and of its scale), rows of
    w_down. Exact (relu(0)^2 = 0, and a zero row of w_down adds nothing);
    the grouped-product kernel takes whole (128, 128) tiles
    (engine/moe_gmm.py `kernel_runs`), and a stack it declines is widened
    to bf16 every forward."""
    from dynamo_tpu.engine.quant import QTensor

    moe = dict(layers["moe"])
    width = moe["w_up"].shape[-1]
    pad = -width % multiple
    if not pad:
        return layers

    def padded(w, axis):
        def one(a, fill=0):
            if a.shape[axis] == 1:         # a scale along the padded axis
                return a
            cfg_ = [(0, 0)] * a.ndim
            cfg_[axis] = (0, pad)
            return jnp.pad(a, cfg_, constant_values=fill)

        if isinstance(w, QTensor):
            return dataclasses.replace(w, q=one(w.q), s=one(w.s, 1))
        return one(w)

    moe["w_up"] = padded(moe["w_up"], -1)
    moe["w_down"] = padded(moe["w_down"], -2)
    return {**layers, "moe": moe}


def init_cache(cfg: NemotronHConfig, num_pages: int, num_slots: int = 2
               ) -> tuple[tuple, tuple]:
    """(k_cache, v_cache): one pair a layer that has state, in layer order.
    A Mamba layer's pair is (convolution tail (S, K-1, C) in the
    activations' dtype, SSM state (S, H, P, N) float32), `num_slots` = S
    slots with slot 0 scratch; an attention layer's pair is its K and V
    pages, as models/llama.py. Each its own array, so every update is in
    place."""
    tail, ssm = state_shapes(cfg, num_slots)
    kv = kv_layer_shape(cfg, num_pages)
    first, second = [], []
    for kind, _, _ in cfg.table:
        if kind == "mamba":
            first.append(jnp.zeros(*tail))
            second.append(jnp.zeros(*ssm))
        elif kind == "attn":
            first.append(jnp.zeros(kv, cfg.dtype))
            second.append(jnp.zeros(kv, cfg.dtype))
    return tuple(first), tuple(second)


def _layer_params(params: dict, kind: str, i: int) -> dict:
    """Static slice of layer i of its kind. An expert layer also carries
    `expert_stacks`, which the grouped product's kernel indexes by layer
    itself (models/llama.py `_layer_params`)."""
    stack = params["layers"][kind]
    lp = jax.tree.map(lambda w: w[i], stack)
    if kind == "moe":
        lp["expert_stacks"] = (
            {k: stack[k] for k in ("w_up", "w_down")}, i)
    return lp


# ---------------------------------------------------------------------------
# The recurrence
# ---------------------------------------------------------------------------


def ssm_chunk_scan(x, dt, a, b, c, s0, chunk: int):
    """The recurrence over a chunk of T tokens in blocks of `chunk`:
    within a block the masked C B^T product, between blocks the carried
    state. x (Bp, T, H, P), dt (Bp, T, H) (0 at a masked position: the
    state stands still there), a (H,) = -exp(A_log), b, c (Bp, T, G, N),
    s0 (Bp, H, P, N) the state before the chunk; float32 throughout, full
    matmul precision. Returns (y (Bp, T, H, P) without the D skip, the
    state after the last token)."""
    bp, t, h, p = x.shape
    g = b.shape[2]
    q = math.gcd(t, chunk)
    nc, rep = t // q, h // g

    def blocks(v):
        return v.reshape((bp, nc, q) + v.shape[2:])

    xb = blocks(x * dt[..., None])                      # (Bp, nc, q, H, P)
    bb, cb = blocks(b), blocks(c)                       # (Bp, nc, q, G, N)
    cum = jnp.cumsum(blocks(dt * a), axis=2)            # (Bp, nc, q, H) <= 0
    # within a block: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) x_j
    cbt = jnp.einsum("bkign,bkjgn->bkgij", cb, bb, precision=_HIGHEST)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (Bp,nc,i,j,H)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    mix = jnp.repeat(cbt, rep, axis=2).transpose(0, 1, 3, 4, 2) * decay
    y = jnp.einsum("bkijh,bkjhp->bkihp", mix, xb, precision=_HIGHEST)
    # what a block adds to the state at its end, and the state's decay
    # over the block
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (Bp, nc, q, H)
    b_heads = jnp.repeat(bb, rep, axis=3)               # (Bp, nc, q, H, N)
    c_heads = jnp.repeat(cb, rep, axis=3)
    added = jnp.einsum("bkjh,bkjhp,bkjhn->bkhpn", to_end, xb, b_heads,
                       precision=_HIGHEST)
    state = s0
    carried = []
    for k in range(nc):
        carried.append(state)
        state = state * jnp.exp(cum[:, k, -1])[..., None, None] \
            + added[:, k]
    before = jnp.stack(carried, axis=1)                 # (Bp, nc, H, P, N)
    y = y + jnp.einsum("bkihn,bkhpn,bkih->bkihp", c_heads, before,
                       jnp.exp(cum), precision=_HIGHEST)
    return y.reshape(bp, t, h, p), state


def ssm_kernel_runs(heads: int, head_dim: int, state: int) -> bool:
    """The Pallas kernel holds a head's state as (head_dim, state) tiles,
    the heads of a lane side by side in one tile's lanes, and a lane's
    whole state as one buffer of its queue, which a phase must hold."""
    return (use_pallas() and state % 128 == 0 and head_dim % 8 == 0
            and heads <= 128
            and heads * head_dim * state * 4 <= _SSM_PHASE_BYTES)


def ssm_decode_update(state, slots, x, dt, a, b, c, d, *, interpret=None):
    """One step of the recurrence a lane, in place at the lane's slot.
    state (S, H, P, N) float32; slots (B,); x (B, H, P), dt (B, H) (0 for
    an invalid lane: its slot stands still), a, d (H,), b, c (B, G, N);
    float32. Returns (y (B, H, P) with the D skip, state). The kernel
    where it runs (`ssm_kernel_runs`), its XLA twin elsewhere."""
    heads, p, n = state.shape[1:]
    if interpret is None and not ssm_kernel_runs(heads, p, n):
        return _ssm_decode_update_xla(state, slots, x, dt, a, b, c, d)
    return _ssm_decode_update_kernel(state, slots, x, dt, a, b, c, d,
                                     interpret=interpret or False)


def _ssm_decode_update_xla(state, slots, x, dt, a, b, c, d):
    rep = x.shape[1] // b.shape[1]
    bh, ch = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
    s = state[slots] * jnp.exp(dt * a)[..., None, None] \
        + (x * dt[..., None])[..., None] * bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s, ch, precision=_HIGHEST) \
        + x * d[None, :, None]
    return y, state.at[slots].set(s)


def _ssm_decode_update_kernel(state, slots, x, dt, a, b, c, d, *,
                              interpret=False):
    """Grid: one step a BATCH of lanes, as many as divide the lanes and
    fill `_SSM_PHASE_BYTES`. The state never leaves its slots but through
    the kernel's own queue (`input_output_aliases`, no gathered copy): a
    batch's lanes are fetched into VMEM buffers, updated where they lie
    and written back to the slots they came from. The copies run in
    PHASES, all of a batch's reads, then all of the batch before's writes,
    never both at once: a read and a write stream that share the bus reach
    81% of its bandwidth, a stream at a time 85% (PERF.md §6, PR 50). The
    update of a batch runs under the write phase of the one before.

    A head's tile is (P, N): P in sublanes, N in lanes. What varies along
    N (B, C) comes as rows; what varies along P (x) comes transposed,
    (P, heads in lanes), so that a head's column is a lane slice; a head's
    decay is a scalar in SMEM; y leaves transposed as x came. The body is
    two passes over a batch, each a loop of its own: the updates (a lane
    broadcast of x's column a tile), then the sums over N (a lane
    reduction a tile). In ONE instruction stream the two kinds of
    cross-lane work take 7.8 us a lane, 1.3 and 1.1 us apart: the unit
    serves one kind at a time.

    Two invalid lanes share slot 0 and every buffer is written back with
    what ITS lane read and updated, never with another's, so a live slot
    (one lane's) is read once and written once; slot 0 (dt = 0) only ever
    receives the bits it holds."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, p = x.shape
    groups, n = b.shape[1:]
    rep = heads // groups
    hl = -(-heads // 128) * 128
    f32 = jnp.float32
    fit = min(lanes, _SSM_PHASE_BYTES // (heads * p * n * 4))
    kk = max(k for k in range(1, fit + 1) if lanes % k == 0)
    steps = lanes // kk
    pad = ((0, 0), (0, 0), (0, hl - heads))
    xt = jnp.pad(jnp.swapaxes(x, 1, 2), pad)                # (B, P, hl)
    dtrow = jnp.pad(dt[:, None, :], pad)                    # (B, 1, hl)
    drow = jnp.pad(d, (0, hl - heads))[None, :]
    dec = jnp.exp(dt * a).reshape(-1)                       # (B * H,)

    def kernel(slot_ref, dec_ref, xt_ref, dt_ref, b_ref, c_ref, d_ref,
               s_in, y_ref, s_out, buf, rsem, wsem):
        k = pl.program_id(0)
        cur = k % 2

        def read(step, j):
            return pltpu.make_async_copy(
                s_in.at[slot_ref[step * kk + j]], buf.at[step % 2, j],
                rsem.at[step % 2, j])

        def write(step, j):
            return pltpu.make_async_copy(
                buf.at[step % 2, j], s_out.at[slot_ref[step * kk + j]],
                wsem.at[step % 2, j])

        def start(copy, step):
            for j in range(kk):
                copy(step, j).start()

        def wait(copy, step):
            for j in range(kk):
                copy(step, j).wait()

        # this batch's reads were started a step ago; once they are in,
        # the bus is the batch before's writes', and the body runs under them
        pl.when(k == 0)(lambda: start(read, k))
        wait(read, k)
        pl.when(k > 0)(lambda: start(write, k - 1))

        def update(j, carry):
            xdt = xt_ref[j] * dt_ref[j]                     # (P, hl)
            for h in range(heads):
                g = h // rep
                buf[cur, j, h] = dec_ref[(k * kk + j) * heads + h] \
                    * buf[cur, j, h] \
                    + xdt[:, h:h + 1] * b_ref[j, g:g + 1, :]
            return carry

        def readout(j, carry):
            lane = jax.lax.broadcasted_iota(jnp.int32, (p, hl), 1)
            acc = jnp.zeros((p, hl), f32)
            for h in range(heads):
                g = h // rep
                out = jnp.sum(buf[cur, j, h] * c_ref[j, g:g + 1, :],
                              axis=-1, keepdims=True)       # (P, 1)
                acc = jnp.where(lane == h, out, acc)
            y_ref[j] = acc + xt_ref[j] * d_ref[...]
            return carry

        jax.lax.fori_loop(0, kk, update, 0)
        jax.lax.fori_loop(0, kk, readout, 0)

        # the next batch's reads fill the buffers those writes empty
        pl.when(k > 0)(lambda: wait(write, k - 1))
        pl.when(k + 1 < steps)(lambda: start(read, k + 1))

        @pl.when(k == steps - 1)
        def _():
            start(write, k)
            wait(write, k)

    def batch_block(*shape):
        return pl.BlockSpec((kk, *shape),
                            lambda k, *_: (k,) + (0,) * len(shape))

    in_place = pl.BlockSpec(memory_space=pl.ANY)
    yt, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[batch_block(p, hl), batch_block(1, hl),
                      batch_block(groups, n), batch_block(groups, n),
                      pl.BlockSpec((1, hl), lambda k, *_: (0, 0)),
                      in_place],
            out_specs=[batch_block(p, hl), in_place],
            scratch_shapes=[pltpu.VMEM((2, kk, heads, p, n), f32),
                            pltpu.SemaphoreType.DMA((2, kk)),
                            pltpu.SemaphoreType.DMA((2, kk))]),
        out_shape=[jax.ShapeDtypeStruct((lanes, p, hl), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={7: 1},        # the state, updated in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_SSM_VMEM_BYTES),
        interpret=interpret,
        name="ssm_decode_update",
    )(slots.astype(jnp.int32), dec, xt, dtrow, b, c, drow, state)
    return jnp.swapaxes(yt[:, :, :heads], 1, 2), state


# ---------------------------------------------------------------------------
# Mixers
# ---------------------------------------------------------------------------


def _ssm_inputs(zxbcdt, cfg: NemotronHConfig):
    di, c = cfg.d_inner, cfg.conv_dim
    return (zxbcdt[..., :di], zxbcdt[..., di:di + c],
            zxbcdt[..., di + c:].astype(jnp.float32))


def _split_xbc(xbc, cfg: NemotronHConfig):
    """The convolved (…, C) float32 as x (…, H, P), B and C (…, G, N)."""
    di, gn = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(lead + (cfg.mamba_heads,
                                          cfg.mamba_head_dim)),
            xbc[..., di:di + gn].reshape(lead + (cfg.ssm_groups,
                                                 cfg.ssm_state)),
            xbc[..., di + gn:].reshape(lead + (cfg.ssm_groups,
                                               cfg.ssm_state)))


def _ssm_out(y, z, lp: dict, cfg: NemotronHConfig):
    """Gate, then the norm over each group, then out_proj. y (…, H, P)
    float32, z (…, d_inner)."""
    with jax.named_scope("ssm_out"):
        lead = z.shape[:-1]
        gated = y.reshape(lead + (cfg.d_inner,)) \
            * jax.nn.silu(z.astype(jnp.float32))
        grouped = gated.reshape(lead + (cfg.ssm_groups, -1))
        scale = jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1,
                                       keepdims=True) + cfg.rms_eps)
        normed = (grouped * scale).reshape(gated.shape) * lp["gnorm"]
        return qm(normed.astype(z.dtype), lp["out_proj"])


def _conv(window, lp: dict, kernel: int):
    """Depthwise causal convolution and silu. window (…, T + K - 1, C),
    the K - 1 inputs before the chunk first. -> (…, T, C) float32."""
    t = window.shape[-2] - (kernel - 1)
    w = window.astype(jnp.float32)
    out = lp["conv_b"]
    for j in range(kernel):
        out = out + lp["conv_w"][j] * lax.slice_in_dim(w, j, j + t, axis=-2)
    return jax.nn.silu(out)


def mamba_prefill(hn, lp: dict, tail, ssm, slots, cached_lens, seq_lens,
                  cfg: NemotronHConfig):
    """A Mamba-2 mixer over a round of prefill chunks. hn (Bp, T, E) the
    normed input; tail (S, K-1, C), ssm (S, H, P, N) the layer's state.
    A first chunk (`cached_lens == 0`) starts from zero whatever its slot
    holds; positions at or past `seq_lens - cached_lens` are masked; the
    new tail is the last K - 1 REAL inputs (a chunk shorter than that
    keeps part of the old tail). Returns (out (Bp, T, E), tail, ssm)."""
    k = cfg.conv_kernel
    t = hn.shape[1]
    with jax.named_scope("ssm_in_proj"):
        z, xbc, dt = _ssm_inputs(qm(hn, lp["in_proj"]), cfg)
    n_real = seq_lens - cached_lens                          # (Bp,)
    fresh = (cached_lens == 0)
    with jax.named_scope("ssm_conv"):
        old = jnp.where(fresh[:, None, None], 0, tail[slots])
        window = jnp.concatenate([old.astype(xbc.dtype), xbc], axis=1)
        take = n_real[:, None] + jnp.arange(k - 1)[None, :]  # (Bp, K-1)
        new_tail = jnp.take_along_axis(window, take[..., None], axis=1)
        tail = tail.at[slots].set(new_tail.astype(tail.dtype))
        x, b, c = _split_xbc(_conv(window, lp, k), cfg)
    with jax.named_scope("ssm_scan"):
        real = jnp.arange(t)[None, :] < n_real[:, None]      # (Bp, T)
        dt = jnp.where(real[..., None],
                       jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
        s0 = jnp.where(fresh[:, None, None, None], 0.0, ssm[slots])
        y, state = ssm_chunk_scan(x, dt, -jnp.exp(lp["A_log"]), b, c, s0,
                                  cfg.chunk_size)
        y = y + x * lp["D"][:, None]
        ssm = ssm.at[slots].set(state)
    return _ssm_out(y, z, lp, cfg), tail, ssm


def mamba_decode(hn, lp: dict, tail, ssm, slots, valid,
                 cfg: NemotronHConfig):
    """A Mamba-2 mixer, one token a lane. hn (B, E). Invalid lanes point at
    slot 0 and leave it as it is."""
    k = cfg.conv_kernel
    with jax.named_scope("ssm_in_proj"):
        z, xbc, dt = _ssm_inputs(qm(hn, lp["in_proj"]), cfg)
    with jax.named_scope("ssm_conv"):
        old = tail[slots]                                    # (B, K-1, C)
        window = jnp.concatenate([old, xbc[:, None].astype(old.dtype)],
                                 axis=1)
        tail = tail.at[slots].set(
            jnp.where(valid[:, None, None], window[:, 1:], old))
        x, b, c = _split_xbc(_conv(window, lp, k)[:, 0], cfg)
    with jax.named_scope("ssm_update"):
        dt = jnp.where(valid[:, None],
                       jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
        y, ssm = ssm_decode_update(ssm, slots, x, dt,
                                   -jnp.exp(lp["A_log"]), b, c, lp["D"])
    return _ssm_out(y, z, lp, cfg), tail, ssm


def _qkv(hn, lp: dict, cfg: NemotronHConfig):
    """q (…, H, D), k, v (…, KVH, D): no bias and no rotation (the family
    has no position embedding; the order lives in the Mamba layers)."""
    with jax.named_scope("attn_qkv"):
        heads = hn.shape[:-1] + (-1, cfg.head_dim)
        return (qm(hn, lp["wq"]).reshape(heads),
                qm(hn, lp["wk"]).reshape(heads),
                qm(hn, lp["wv"]).reshape(heads))


def _attn_out(attn, lp: dict):
    with jax.named_scope("attn_out"):
        return qm(attn.reshape(attn.shape[:-2] + (-1,)), lp["wo"])


# ---------------------------------------------------------------------------
# Entries (the names the engine dispatches and the readers search)
# ---------------------------------------------------------------------------


def _paged_forward(params, k_cache, v_cache, tokens, page_tables,
                   cached_lens, seq_lens, slots, cfg, aligned):
    x = params["embed"][tokens]                              # (Bp, T, E)
    _, write = _chunk_kv(page_tables, cached_lens, seq_lens,
                         tokens.shape[1], cfg, aligned)
    first, second = list(k_cache), list(v_cache)
    for kind, i, ci in cfg.table:
        lp = _layer_params(params, kind, i)
        hn = rms_norm(x, lp["norm"], cfg.rms_eps)
        if kind == "mamba":
            out, first[ci], second[ci] = mamba_prefill(
                hn, lp, first[ci], second[ci], slots, cached_lens,
                seq_lens, cfg)
        elif kind == "moe":
            with jax.named_scope("mlp"):
                out = moe_mlp(hn, lp, cfg)
        else:
            q, k, v = _qkv(hn, lp, cfg)
            first[ci], second[ci] = write(first[ci], second[ci], k, v)
            with jax.named_scope("attn_core"):
                attn = paged_attention_prefill(
                    q, first[ci], second[ci], page_tables, cached_lens,
                    seq_lens, page_size=cfg.page_size)
            out = _attn_out(attn, lp)
        x = x + out
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x, tuple(first), tuple(second)


@partial(jax.jit, static_argnames=("cfg", "aligned"), donate_argnums=(1, 2))
def prefill_batch(params: dict, k_cache: tuple, v_cache: tuple,
                  tokens: jax.Array, page_tables: jax.Array,
                  cached_lens: jax.Array, seq_lens: jax.Array,
                  cfg: NemotronHConfig, aligned: bool = False, *,
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
    for kind, i, ci in cfg.table:
        lp = _layer_params(params, kind, i)
        hn = rms_norm(x, lp["norm"], cfg.rms_eps)
        if kind == "mamba":
            out, first[ci], second[ci] = mamba_decode(
                hn, lp, first[ci], second[ci], slots, valid, cfg)
        elif kind == "moe":
            with jax.named_scope("mlp"):
                out = moe_mlp(hn, lp, cfg)
        else:
            q, k, v = _qkv(hn, lp, cfg)
            with jax.named_scope("kv_write"):
                first[ci], second[ci] = _write_kv(
                    first[ci], second[ci], k, v, page_ids, offsets, valid)
            with jax.named_scope("attn_core"):
                attn = paged_attention_decode(
                    q, first[ci], second[ci], lengths, page_tables,
                    page_size=cfg.page_size)
            out = _attn_out(attn, lp)
        x = x + out
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
                      top_k: jax.Array, cfg: NemotronHConfig,
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
def forward_logits(params: dict, tokens: jax.Array, cfg: NemotronHConfig
                   ) -> jax.Array:
    """Every position's logits (T, V) of ONE sequence in one pass, no
    cache: the prefill entry over fresh state and pages of its own. For
    tests."""
    t = tokens.shape[0]
    pages = -(-t // cfg.page_size)
    kc, vc = init_cache(cfg, pages + 1, 2)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    x, _, _ = _paged_forward(
        params, kc, vc, tokens[None], table, jnp.zeros(1, jnp.int32),
        jnp.full(1, t, jnp.int32), jnp.ones(1, jnp.int32), cfg, False)
    return qm(x[0], params["lm_head"]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Checkpoint (`backbone.layers.{i}.mixer.*`)
# ---------------------------------------------------------------------------

# ours -> the checkpoint's tensor under `backbone.layers.{i}.`, by kind;
# `t`: stored (out, in), transposed on the way in; `f32`: kept float32
_TENSORS = {
    "mamba": (("norm", "norm.weight", "f32"),
              ("in_proj", "mixer.in_proj.weight", "t"),
              ("conv_w", "mixer.conv1d.weight", "conv"),
              ("conv_b", "mixer.conv1d.bias", "f32"),
              ("dt_bias", "mixer.dt_bias", "f32"),
              ("A_log", "mixer.A_log", "f32"),
              ("D", "mixer.D", "f32"),
              ("gnorm", "mixer.norm.weight", "f32"),
              ("out_proj", "mixer.out_proj.weight", "t")),
    "attn": (("norm", "norm.weight", "f32"),
             ("wq", "mixer.q_proj.weight", "t"),
             ("wk", "mixer.k_proj.weight", "t"),
             ("wv", "mixer.v_proj.weight", "t"),
             ("wo", "mixer.o_proj.weight", "t")),
    "moe": (("norm", "norm.weight", "f32"),
            ("router", "mixer.gate.weight", "tf32"),
            ("router_bias", "mixer.gate.e_score_correction_bias", "f32"),
            ("w_shared_up", "mixer.shared_experts.up_proj.weight", "t"),
            ("w_shared_down", "mixer.shared_experts.down_proj.weight", "t")),
}
_EXPERTS = (("w_up", "up_proj"), ("w_down", "down_proj"))


def checkpoint_names(cfg: NemotronHConfig) -> list:
    """Every tensor of the checkpoint, in the order `load_params` reads."""
    names = []
    for layer, (kind, _, _) in enumerate(cfg.table):
        p = f"backbone.layers.{layer}."
        names += [p + name for _, name, _ in _TENSORS[kind]]
        if kind == "moe":
            names += [p + f"mixer.experts.{e}.{w}.weight"
                      for _, w in _EXPERTS for e in range(cfg.num_experts)]
    return names + ["backbone.embeddings.weight", "backbone.norm_f.weight",
                    "lm_head.weight"]


def load_params(path: str, cfg: NemotronHConfig, quantize=None) -> dict:
    """Checkpoint -> param pytree on the default device, as
    models/loader.py `load_llama_params_device`: reads on a prefetch
    thread, transpose / cast / int8 on the device tensor by tensor
    (quantize before stack, so transients stay int8), the expert width
    padded to whole kernel tiles (`pad_expert_width`, before the scales
    are taken: a zero column quantizes to zeros)."""
    from dynamo_tpu.engine.quant import (QUANT_KEYS, QTensor,
                                         _lm_head_quant_ok, quantize as q8)
    from dynamo_tpu.models.loader import _Prefetcher, _TensorIndex

    if quantize not in (None, False, "int8"):
        raise ValueError("nemotron_h serves bf16 or weight-only int8")
    idx = _TensorIndex(path)
    pf = _Prefetcher(idx, checkpoint_names(cfg))
    pad = -cfg.intermediate_size % 128
    pending = []

    def throttle(out):
        pending.append(out)
        if len(pending) >= 8:
            jax.block_until_ready(pending.pop())
            pending.clear()
        return out

    @partial(jax.jit, static_argnames=("how", "pad_axis"))
    def prep(w, how, pad_axis=None):
        if how == "conv":                       # (C, 1, K) -> (K, C)
            return jnp.transpose(w[:, 0, :]).astype(jnp.float32)
        if how in ("t", "tf32"):
            w = jnp.transpose(w)
        if pad_axis is not None and pad:
            widths = [(0, 0), (0, 0)]
            widths[pad_axis] = (0, pad)
            w = jnp.pad(w, widths)
        return w.astype(jnp.float32 if how in ("f32", "tf32")
                        else cfg.dtype)

    quant = jax.jit(q8, donate_argnums=(0,))

    def tensor(name, how, key, pad_axis=None):
        w = throttle(prep(jax.device_put(pf.get(name)), how, pad_axis))
        if quantize and key in QUANT_KEYS:
            w = quant(w)
            throttle(w.q)
        return w

    def stack(ws):
        if isinstance(ws[0], QTensor):
            return QTensor(q=jnp.stack([w.q for w in ws]),
                           s=jnp.stack([w.s for w in ws]))
        return jnp.stack(ws)

    try:
        by_kind = {kind: {key: [] for key, _, _ in _TENSORS[kind]}
                   for kind in _TENSORS}
        by_kind["moe"].update({key: [] for key, _ in _EXPERTS})
        for layer, (kind, _, _) in enumerate(cfg.table):
            p = f"backbone.layers.{layer}."
            for key, name, how in _TENSORS[kind]:
                by_kind[kind][key].append(tensor(p + name, how, key))
            if kind == "moe":
                for (key, w), axis in zip(_EXPERTS, (1, 0)):
                    by_kind[kind][key].append(stack([
                        tensor(p + f"mixer.experts.{e}.{w}.weight", "t",
                               key, axis)
                        for e in range(cfg.num_experts)]))
        # a kind's layers stacked key by key, its pieces dropped as they
        # are: both copies of every expert stack at once were 5 GB of peak
        layers = {kind: {key: stack(d.pop(key)) for key in list(d)}
                  for kind, d in by_kind.items()}
        params = {
            "layers": layers,
            "embed": tensor("backbone.embeddings.weight", "", "embed"),
            "final_norm": tensor("backbone.norm_f.weight", "f32", "norm"),
        }
        lm = tensor("lm_head.weight", "t", "lm_head")
        params["lm_head"] = quant(lm) \
            if quantize and _lm_head_quant_ok(lm) else lm
        jax.block_until_ready(params)
        return params
    finally:
        pf.stop()
        idx.close()
