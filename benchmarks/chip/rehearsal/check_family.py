"""A family is files: moving the llama family's two functions out of
`lib/ckpt.py` and `lib/costs.py` changed no weight and no byte count.

    python3 benchmarks/chip/rehearsal/check_family.py

- the checkpoints of the two toy configurations at seed 2147483999 have,
  file by file, the sha256 that PR 28's writer gave (pinned below);
- the markers of the two full-size configurations are PR 28's (computed,
  nothing written), so a checkpoint written by the parent is reused;
- `families/llama.decode_step_bytes` gives PR 28's `lib/costs.py` values at
  kv_tokens 19200 and 6600, to the byte;
- a family's own fills reach the writer: zeros, a gain and a fan-in of its
  own (a made-up family, written to a temporary directory).
"""

import hashlib
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import ckpt, family                                     # noqa: E402

SEED = 2147483999
TOKENIZER = {
    "tokenizer.json":
        "d6a5a3db24926d0d5dc62dbf129b9283ba20ee2fe3faf9793ab74d0fe38980f2",
    "tokenizer_config.json":
        "f4d828300961a70108a8decdeba8ce9fad3aade272800169fd41a95720fef803"}
PARENT_FILES = {
    "tiny-mistral": {
        ".bench_ckpt":
            "a857e04e9850d3e7a648a76a77c1a1d7b5f09355b0d1ab3e11ea8345a611851d",
        "config.json":
            "16ac4bceb78814e72eea4e07dc7acc91a4f622f11b881e087b1a72bc5a2ffc38",
        "model-00000.safetensors":
            "8c4601e23d1ddc141dd0eaaa52426cc2661c449c2db4fcbde248f719145a136a",
        "model.safetensors.index.json":
            "68588203d81472b7217285caefea31f978d08a62057ff2b2ab3bf569eb1e44b6",
        **TOKENIZER},
    "tiny-qwen": {
        ".bench_ckpt":
            "63a7a60b5e943a9a0a827908fdd8d072b72cf971d4e0e789c44f2310b500f16e",
        "config.json":
            "4059a0ee4c74b9321e6326867834360f3994277c3cd8dc0b221ea5d009079bf6",
        "model-00000.safetensors":
            "46b859b75d32d129067fa7221375e7f82d1894bdd71d50bab9741e8f0cfb7220",
        "model.safetensors.index.json":
            "d5beeb0e807d94eafa3f65286032237067aef22f42c0dd3cb64eba3e95abaa74",
        **TOKENIZER}}
PARENT_MARKERS = {      # (configuration, seed) -> .bench_ckpt of PR 28
    ("mistral-7b-v0.3-int8", 0):
        "877e9ea4bdd77706c24b95fc711198ac864b51f55f855df6351ea8943628e91f",
    ("mistral-7b-v0.3-int8", SEED):
        "3a3b551d4ab05954b68ea79516efbecc497d2d92c686bd568e95b37bbe9ac62b",
    ("qwen2.5-7b-int8", 0):
        "5c4180f1b778d80c2ae8a3cc0074ff9531197f5492a4da468e81b46e84ec84d1",
    ("qwen2.5-7b-int8", SEED):
        "110374a76c288cf790caaaf5376d7e112ea727b9088005a6d177b09eced70ffa"}
PARENT_BYTES = {        # (configuration, kv_tokens) -> lib/costs.py of PR 28
    ("mistral-7b-v0.3-int8", 19200): 9630121984,
    ("mistral-7b-v0.3-int8", 6600): 7978614784,
    ("qwen2.5-7b-int8", 19200): 8716288000,
    ("qwen2.5-7b-int8", 6600): 7993753600}


def load(rel: str) -> dict:
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL {what}")


def same_weights() -> None:
    for name, want in PARENT_FILES.items():
        with tempfile.TemporaryDirectory() as d:
            ckpt.write_checkpoint(d, load(f"rehearsal/{name}.json"), SEED)
            got = {}
            for fname in sorted(os.listdir(d)):
                with open(os.path.join(d, fname), "rb") as f:
                    got[fname] = hashlib.sha256(f.read()).hexdigest()
        check(got == want, f"{name}: checkpoint differs from the parent's: "
              f"{[f for f in want if got.get(f) != want[f]]}")
    for (name, seed), want in PARENT_MARKERS.items():
        check(ckpt.marker_of(load(f"configs/{name}.json"), seed) == want,
              f"{name} seed {seed}: the marker is not the parent's")


def same_bytes() -> None:
    for (name, kv), want in PARENT_BYTES.items():
        config = load(f"configs/{name}.json")
        for lanes in (1.0, 7.5, 32.0):
            got = family.load("families", config).decode_step_bytes(
                config, kv, lanes)
            check(got == want, f"{name} kv {kv}: {got} bytes, the parent "
                  f"counted {want}")


class MadeUp:
    """A family with a zero fill, a gain and a fan-in of its own."""

    @staticmethod
    def fills(hf):
        return {"bias0": {"fill": "zeros"},
                "latent_out": {"fill": "noise", "fan_in": hf["latent"]},
                "router": {"fill": "noise", "gain": 4.0}}

    @staticmethod
    def tensor_specs(hf):
        return [("a.norm", (8,), "norm"), ("a.bias", (8,), "bias0"),
                ("a.out", (64, hf["latent"]), "latent_out"),
                ("a.dense", (64, hf["hidden_size"]), "dense"),
                ("a.router", (64, hf["hidden_size"]), "router")]


def own_fills() -> None:
    import numpy as np
    from safetensors.numpy import load_file

    hf = {"hidden_size": 64, "latent": 16}
    fills = ckpt.fills_of(MadeUp, hf, 16.0)
    with tempfile.TemporaryDirectory() as d:
        ckpt._write_shard(d, 0, MadeUp.tensor_specs(hf), SEED, 64, fills)
        t = {k: np.asarray(v, dtype=np.float32) for k, v in load_file(
            os.path.join(d, "model-00000.safetensors")).items()}
    check((t["a.norm"] == 1).all() and (t["a.bias"] == 0).all(),
          "ones and zeros")
    low = {k: float(np.abs(v).min()) for k, v in t.items()}
    # one exponent a tensor: |w| in [2^e, 2^(e+1)); a quarter of the fan-in
    # doubles it, a gain of 4 quadruples it
    check(low["a.out"] == 2 * low["a.dense"], f"fan-in of its own: {low}")
    check(low["a.router"] == 4 * low["a.dense"], f"gain of its own: {low}")
    try:
        ckpt.fills_of(type("Bad", (), {"fills": staticmethod(
            lambda hf: {"dense": {"fill": "zeros"}})}), hf, 16.0)
    except ValueError:
        return
    check(False, "a family may not redefine the benchmark's own fills")


if __name__ == "__main__":
    same_weights()
    same_bytes()
    own_fills()
    print("check_family: the llama family's weights, markers and byte "
          "counts are PR 28's; a family's own fills reach the writer")
