"""Int8 weight-only quantization for the serving engine.

Decode throughput on TPU is weight-stream-bound: on the r2 bench model
(1.1B bf16, batch 16) the matmul weight read alone is 6.2 ms of the
8.3 ms step (bench.py ablation). Halving weight bytes halves that floor —
the one decode lever left after fused bursts and pallas kernels.

Scheme (reference parity: the reference delegates FP8/INT8 serving to
TRT-LLM engine configs, e.g. recipes' `quantization` knobs; we own the
implementation, TPU-first):
- per-output-channel symmetric int8: for a weight W of shape
  (..., K, N), scale s = absmax over K / 127 with shape (..., 1, N),
  q = round(W / s).
- matmul stays on the MXU in the activation dtype:
  ``x @ W  ==  (x @ q) * s``  exactly, because s is constant along the
  contraction dim. XLA fuses the int8→bf16 convert into the matmul's
  operand read, so HBM traffic is the int8 bytes (verified on v5e:
  see bench.py quant ablation).
- embeddings and norms stay in bf16/fp32 (gather traffic is per-token,
  not per-step; norms are tiny and precision-critical).

`QTensor` is a registered pytree, so quantized params flow through
`jax.jit`, `jax.tree.map` (models/llama.py `_layer_params` static slice
maps over q and s together), donation, and GSPMD sharding unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

# layer-dict keys that get quantized (contraction dim = axis -2): the
# attention and FFN projections, a Mamba-2 layer's in_proj / out_proj and a
# shared expert's two (models/nemotron_h.py, whose layer dict holds one
# dict a kind of layer)
QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
              "in_proj", "out_proj", "w_shared_up", "w_shared_down")


def _map_layers(fn, layers: dict) -> dict:
    """`fn` over the QUANT_KEYS leaves of a layer dict that are not
    QTensors yet, through the dicts by kind of a hybrid stack."""
    return {
        k: (_map_layers(fn, v) if isinstance(v, dict)
            else fn(v) if k in QUANT_KEYS and not isinstance(v, QTensor)
            else v)
        for k, v in layers.items()}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """Weight stored as int8 + per-output-channel fp32 scale.

    bits=8: q is int8 in the original weight shape (..., K, N).
    bits=4: q is int8 holding TWO 4-bit values per byte, packed
        pairwise along the LAST axis — q.shape = (..., K, N//2), with
        logical column 2j in the low nibble of packed column j and
        column 2j+1 in the high nibble. The leaf dtype stays int8, so
        nothing S4-typed ever crosses a jit / device_put boundary:
        placing an S4 array from eager context was seen to recurse
        forever in device_put on jax 0.9, and feeding a `bitcast_convert_type(..., int4)` result
        straight into `dot` MIScompiles on Mosaic (probed: rel err 2.2
        vs the exact shift/mask unpack). Unpacking is therefore plain
        int8 shift arithmetic inside the consuming jit (see _unpack4).
    s: fp32, (..., 1, N) — broadcasts onto the matmul OUTPUT (x @ q) * s.
    """

    q: jax.Array
    s: jax.Array
    bits: int = 8
    # activation precision for the matmul: 16 = exact W8A16/W4A16
    # (convert weights up, dot in the activation dtype); 8 = W8A8 —
    # per-row dynamic int8 activations on the MXU's NATIVE int8 path
    # (2× the bf16 pass rate on v5e; decode is pass-bound). int4 always
    # runs A8 in its pallas kernel regardless of this field.
    act_bits: int = 16

    def tree_flatten(self):
        return (self.q, self.s), (self.bits, self.act_bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        if isinstance(aux, tuple):
            return cls(*children, bits=aux[0], act_bits=aux[1])
        return cls(*children, bits=aux if aux else 8)

    @property
    def shape(self):
        """LOGICAL weight shape (int4 reports the unpacked width)."""
        sh = self.q.shape
        if self.bits == 4:
            return (*sh[:-1], sh[-1] * 2)
        return sh

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def nbytes(self):
        """Physical bytes (the honest HBM accounting: int4 = N/2)."""
        return self.q.nbytes + self.s.nbytes

    @property
    def dtype(self):
        return self.q.dtype


def pack4(q: jax.Array) -> jax.Array:
    """int8 values in [-7, 7] → nibble-packed int8, pairs along the
    last axis (even logical index = low nibble).

    The LOW nibble stores ``lo + 8`` (unsigned, [1, 15]); the HIGH
    nibble stores ``hi`` two's-complement. This makes the signed byte
    EXACTLY ``16*hi + (lo + 8)`` (range [-111, 127], no wrap), which is
    what lets the pallas kernel skip unpacking entirely: it matmuls the
    raw bytes and the AND-masked low nibbles and recovers the two
    nibble products algebraically (engine/int4_mm.py)."""
    assert q.shape[-1] % 2 == 0, q.shape
    lo = jnp.bitwise_and(q[..., 0::2] + 8, 0xF)
    hi = jnp.left_shift(q[..., 1::2], 4)
    return jnp.bitwise_or(lo, hi).astype(jnp.int8)


def _unpack4(p: jax.Array) -> jax.Array:
    """Nibble-packed int8 (..., Np) → int8 values (..., 2*Np).

    Low nibble is bias-8 unsigned (see pack4); high nibble is
    recovered with an arithmetic shift (sign-extends). int8 end to
    end — nothing S4-typed, which matters because S4 both breaks
    device_put from eager context and MIScompiles as a dot operand
    on this runtime (probed on v5e)."""
    lo = jnp.bitwise_and(p, 0xF).astype(jnp.int8) - 8
    hi = jnp.right_shift(p, 4)
    return jnp.stack([lo, hi], axis=-1).reshape(
        *p.shape[:-1], p.shape[-1] * 2)


def quantize(w: jax.Array, bits: int = 8, act_bits: int = 16) -> QTensor:
    """Per-output-channel symmetric int quantization over the
    contraction dim (-2). bits=8 → int8; bits=4 → nibble-packed int8
    (two values per byte, halving weight HBM traffic again over int8 at
    a larger rounding error). act_bits=8 marks the weight for the W8A8
    native-int8-MXU matmul path (qm dispatch); int4 always runs its own
    A8 kernel, so act_bits must stay 16 there (asserted — silently
    dropping the flag would be worse)."""
    assert bits in (8, 4), bits
    assert bits == 8 or act_bits == 16, (bits, act_bits)
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    qmax = (1 << (bits - 1)) - 1
    s = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(wf / s), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        return QTensor(q=pack4(q), s=s, bits=4)
    return QTensor(q=q, s=s, act_bits=act_bits)


def qm(x: jax.Array, w: Any) -> jax.Array:
    """Matmul against a maybe-quantized weight: ``x @ w``.

    For QTensor the convert int8→x.dtype fuses into the matmul operand
    read (weight HBM traffic = int8 bytes); the per-channel scale is one
    elementwise multiply on the (small) output. int4 unpacks nibbles
    with int8 shifts first (see QTensor docstring for why not S4).
    """
    if isinstance(w, QTensor):
        if w.bits == 4:
            return _qm4(x, w)
        if w.act_bits == 8:
            return _qm8a8(x, w)
        y = jnp.dot(x, w.q.astype(x.dtype))
        return y * w.s.astype(x.dtype)
    return x @ w


def _qm8a8(x: jax.Array, w: QTensor) -> jax.Array:
    """W8A8: native int8 MXU dot on TPU (engine/int4_mm.w8a8_matmul);
    plain W8A16 math elsewhere (CPU tests) — activation quantization is
    a TPU-kernel-path approximation, like the int4 path's."""
    from dynamo_tpu.engine.attention import use_pallas

    if use_pallas() and w.q.ndim == 2 and x.shape[-1] % 128 == 0 \
            and w.q.shape[-1] % 128 == 0:
        from dynamo_tpu.engine.int4_mm import w8a8_matmul

        lead = x.shape[:-1]
        y = w8a8_matmul(x.reshape(-1, x.shape[-1]), w.q, w.s)
        return y.reshape(*lead, y.shape[-1])
    y = jnp.dot(x, w.q.astype(x.dtype))
    return y * w.s.astype(x.dtype)


def _qm4(x: jax.Array, w: QTensor) -> jax.Array:
    """int4 matmul: pallas kernel on TPU (int4 HBM traffic), XLA
    unpack elsewhere (CPU tests / odd shapes)."""
    from dynamo_tpu.engine.attention import use_pallas

    if use_pallas() and w.q.ndim == 2 and x.shape[-1] % 128 == 0 \
            and w.q.shape[-1] % 128 == 0:
        from dynamo_tpu.engine.int4_mm import int4_matmul

        lead = x.shape[:-1]
        y = int4_matmul(x.reshape(-1, x.shape[-1]), w.q, w.s)
        return y.reshape(*lead, y.shape[-1])
    y = jnp.dot(x, _unpack4(w.q).astype(x.dtype))
    return y * w.s.astype(x.dtype)


# Above this vocab width the int8 lm_head matmul sends the XLA/Mosaic
# compile into a tailspin (measured on v5e: an 8-layer llama3-8b decode
# burst compiles in 9 s with a bf16 lm_head vs 168 s with int8 at
# V=128256; V=32000 int8 is fine). The bf16 lm_head costs ~0.5 GB HBM
# and ~1 ms/step on an 8B — the compile cliff costs minutes per shape.
LM_HEAD_QUANT_MAX_VOCAB = 65536


def _lm_head_quant_ok(w) -> bool:
    return w.shape[-1] <= LM_HEAD_QUANT_MAX_VOCAB


def _bits_of(mode) -> int:
    return 4 if mode in (4, "int4") else 8


def _act_bits_of(mode) -> int:
    return 8 if mode == "w8a8" else 16


def quantize_params(params: dict, quantize_lm_head: bool = True,
                    mode: str = "int8") -> dict:
    """Quantize the llama-layout param pytree (models/llama.py init_params).

    Pure jnp — run under `jax.jit` (optionally with donation) so sharded
    params quantize in place on their devices without a host bounce.
    Idempotent: leaves that are already QTensor pass through, so
    host-pre-quantized checkpoints (quantize_params_host) can flow
    through an engine configured with quantize="int8" unchanged.
    """
    bits = _bits_of(mode)
    act_bits = _act_bits_of(mode)
    out = dict(params)
    out["layers"] = _map_layers(
        lambda v: quantize(v, bits, act_bits), params["layers"])
    if quantize_lm_head and "lm_head" in params \
            and not isinstance(params["lm_head"], QTensor) \
            and _lm_head_quant_ok(params["lm_head"]):
        # lm_head stays int8 even under int4: the output head is the
        # quality-critical matmul and its rounding error lands directly
        # on the logits
        out["lm_head"] = quantize(params["lm_head"], 8)
    return out


def quantize_host(w) -> QTensor:
    """quantize() in host numpy: same scheme, no device involvement."""
    import numpy as np

    wf = np.asarray(w).astype(np.float32)
    amax = np.max(np.abs(wf), axis=-2, keepdims=True)
    s = np.maximum(amax, 1e-12) / 127.0
    q = np.clip(np.rint(wf / s), -127, 127).astype(np.int8)
    return QTensor(q=q, s=s)


def quantize_params_host(params: dict,
                         quantize_lm_head: bool = True) -> dict:
    """Host-side int8 quantization of a loaded (numpy) checkpoint.

    This is the independent REFERENCE implementation the differential
    tests check the device paths against (tests/test_quant.py,
    tests/test_weights.py) — production loads go through
    models/loader.load_llama_params_device, which quantizes on the
    accelerator (numpy over ml_dtypes bf16 is emulated and takes tens
    of minutes at 8B scale on a small host)."""
    out = dict(params)
    out["layers"] = _map_layers(quantize_host, params["layers"])
    if quantize_lm_head and "lm_head" in params \
            and not isinstance(params["lm_head"], QTensor) \
            and _lm_head_quant_ok(params["lm_head"]):
        out["lm_head"] = quantize_host(params["lm_head"])
    return out


def quantize_params_jit(params: dict, donate: bool = True,
                        mode: str = "int8") -> dict:
    """Device-side quantization; donates the bf16 buffers so peak memory
    is ~1.5× the bf16 params, not 2.5×."""
    fn = jax.jit(functools.partial(quantize_params, mode=mode),
                 donate_argnums=(0,) if donate else ())
    return fn(params)


def scale_spec(q_spec, s_ndim: int):
    """PartitionSpec for a QTensor's scale given its weight's spec: all
    dims but the last are size-1 (unshardable), the last matches the
    weight's output-dim sharding."""
    from jax.sharding import PartitionSpec as P

    spec = tuple(q_spec) if q_spec is not None else ()
    last = spec[s_ndim - 1] if len(spec) >= s_ndim else None
    return P(*([None] * (s_ndim - 1)), last)
