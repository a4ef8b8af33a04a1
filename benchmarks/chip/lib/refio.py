"""What every family's reference needs and none should write twice: the
run's checkpoint tensor by tensor, the stated weight precision as a rule,
and the padding that keeps the compiled shapes few. JAX: only the
comparison's child imports this. Nothing of `dynamo_tpu` is imported here or
in `reference/`: the reference takes the checkpoint's bf16 tensors and
nothing that the program has made of them.
"""

from __future__ import annotations

import functools
import json
import os
import struct

import jax
import jax.numpy as jnp
import numpy as np

# A sequence's tokens and its rows of logits are padded up to the next rung,
# so that the compiled shapes are few: a run's new process loads each from
# the compile cache, and a checkout's first run with a shape compiles it
# (~15 s a layer program on the chip). Rungs about 1.4x apart.
TOKEN_BUCKETS = tuple(512 * k for k in (1, 2, 4, 6, 9, 13, 18, 24, 32, 48, 64))
ROW_BUCKETS = (128, 256, 512, 1024, 2048, 4096)


class Checkpoint:
    """name -> tensor of an HF-format safetensors checkpoint, as float32 on
    the default device; the conversion from bf16 is exact. Plain reads in
    the caller's thread: on the chip's machine 1.2 GB/s, and a reader
    thread beside JAX's dispatch made a pass slower, 25 s against 15."""

    def __init__(self, path: str) -> None:
        self.path = path
        with open(os.path.join(path, "model.safetensors.index.json")) as f:
            self._map = json.load(f)["weight_map"]
        self._files: dict = {}

    def __contains__(self, name: str) -> bool:
        return name in self._map

    def _header(self, fname: str) -> tuple[dict, int]:
        """A safetensors file is 8 bytes of header length, a JSON header
        (name -> dtype, shape, data_offsets) and the tensors' bytes."""
        if fname not in self._files:
            with open(os.path.join(self.path, fname), "rb") as f:
                n = struct.unpack("<Q", f.read(8))[0]
                self._files[fname] = (json.loads(f.read(n)), 8 + n)
        return self._files[fname]

    def numpy(self, name: str):
        """The tensor's bytes, viewed as bf16."""
        import ml_dtypes

        fname = self._map[name]
        header, base = self._header(fname)
        entry = header[name]
        if entry["dtype"] != "BF16":
            raise ValueError(f"{name}: {entry['dtype']}, not the bf16 that "
                             "lib/ckpt.py writes")
        start, end = entry["data_offsets"]
        raw = np.fromfile(os.path.join(self.path, fname), dtype=np.uint16,
                          count=(end - start) // 2, offset=base + start)
        return raw.view(ml_dtypes.bfloat16).reshape(entry["shape"])

    def __call__(self, name: str) -> jax.Array:
        return jnp.asarray(self.numpy(name)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("qmax",))
def _round(w, qmax):
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-1, keepdims=True), 1e-12) / qmax
    return jnp.clip(jnp.round(w / s), -qmax, qmax) * s


def as_served(w: jax.Array, bits: int) -> jax.Array:
    """The one departure from the float32 model: a weight that
    `--quantize int8` serves is round(W / s) x s with s = absmax over the
    contraction axis / 127, one s an output channel (the scheme
    `dynamo_tpu/engine/quant.py` states, copied here as a rule). `w` is in
    the checkpoint's layout, (out, in): the contraction axis is the last.
    16 leaves the bf16 weight as it is; 4 is the control's precision
    (absmax / 7)."""
    if bits >= 16:
        return w
    return _round(w, qmax=float((1 << (bits - 1)) - 1))


def bits_of(config: dict, control: str | None) -> dict:
    """{"layers", "lm_head"}: the precision the configuration states
    (`deployment.weight_bytes`: 1 byte is int8, 2 is bf16), or with
    `control` the nearest below it for the layers' weights."""
    wb = config["deployment"]["weight_bytes"]
    bits = {"layers": 8 * int(wb["layers"]), "lm_head": 8 * int(wb["lm_head"])}
    if control == "int4":
        bits["layers"] = 4
    elif control:
        raise ValueError(f"no control precision {control!r}")
    return bits


def pad_to(n: int, buckets: tuple) -> int:
    """The first rung that holds n."""
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"{n} is beyond the last rung, {buckets[-1]}")
