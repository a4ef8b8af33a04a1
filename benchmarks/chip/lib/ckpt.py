"""Synthetic HF-format checkpoint + tokenizer for a benchmark configuration.

Adapted from `dynamo_tpu/models/synth_ckpt.py:write_synthetic_hf_checkpoint`
(PR 23 ran that one on the chip). Differences, all for the benchmark:

- every size and HF key comes from the configuration file, not a preset;
- the weights are bounded random bits from `--seed` (sign and mantissa
  random, exponent fixed: |w| uniform in [s, 2s), mean 0), generated and
  saved shard by shard in a few threads, instead of slices of one pool;
- a `tokenizer.json` (WordLevel over whitespace, fixed-width words
  `t000000`...) is written beside the weights, so the model card says `hf`,
  every generated id detokenises to one word, and a prompt of n words is
  exactly n tokens. No token is declared special: a skipped special token
  would make a frame's word count differ from its token count.

numpy + safetensors only: the benchmark's parent process calls this and must
never import JAX.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import json
import os
import shutil

import numpy as np

from lib import family

# keys of a configuration file that belong to the benchmark, not to the
# model's config.json
BENCH_KEYS = ("source", "assumed", "deployment", "weights", "rehearsal",
              "family")
SHARD_BYTES = 1 << 30
WRITER_THREADS = 6
WORD = "t{:06d}"
BASE_KINDS = ("norm", "dense", "head")      # fills every family has


def hf_config(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in BENCH_KEYS}


def fills_of(fam, hf: dict, head_gain: float) -> dict:
    """kind -> how a tensor of that kind is filled. Every family has `norm`
    (ones), `dense` (noise) and `head` (noise x `head_gain`); a family adds
    kinds of its own by returning them from `fills(hf)`, as data:
    `{"fill": "zeros"}` (a router's bias), `{"fill": "noise", "gain": g,
    "fan_in": n}` (noise of rms 0.4 / sqrt(n) x g; `fan_in` absent means
    `hidden_size`, right for a projection that reads the residual stream
    and wrong for one that reads a narrower latent)."""
    fills = {"norm": {"fill": "ones"}, "dense": {"fill": "noise"},
             "head": {"fill": "noise", "gain": head_gain}}
    own = getattr(fam, "fills", None)
    for kind, fill in (own(hf) if own else {}).items():
        if kind in fills:
            raise ValueError(f"fill {kind!r} is the benchmark's own")
        if fill.get("fill") not in ("ones", "zeros", "noise"):
            raise ValueError(f"fill {kind!r}: unknown {fill.get('fill')!r}")
        fills[kind] = fill
    return fills


def _noise_bf16(rng: np.random.Generator, shape: tuple, scale: float):
    """bf16 values with random sign and mantissa and one exponent: |w|
    uniform in [2^e, 2^(e+1)), where 2^e is the power of two nearest to
    scale / 1.53 (1.53 = rms of a uniform [1, 2) magnitude)."""
    import ml_dtypes

    n = int(np.prod(shape))
    bits = rng.integers(0, 1 << 16, size=n, dtype=np.uint16)
    exponent = int(np.round(np.log2(scale / 1.53))) + 127
    bits &= np.uint16(0x807F)
    bits |= np.uint16(exponent << 7)
    return bits.view(ml_dtypes.bfloat16).reshape(shape)


def _plan_shards(specs: list) -> list[list]:
    shards, cur, cur_n = [], [], 0
    for spec in specs:
        cur.append(spec)
        cur_n += int(np.prod(spec[1])) * 2
        if cur_n >= SHARD_BYTES:
            shards.append(cur)
            cur, cur_n = [], 0
    if cur:
        shards.append(cur)
    return shards


def _write_shard(path: str, index: int, specs: list, seed: int,
                 hidden: int, fills: dict) -> tuple[str, list, int]:
    import ml_dtypes
    from safetensors.numpy import save_file

    rng = np.random.default_rng([seed, index])
    tensors = {}
    for name, shape, kind in specs:
        fill = fills[kind]
        if fill["fill"] != "noise":
            make = np.ones if fill["fill"] == "ones" else np.zeros
            tensors[name] = make(shape, dtype=ml_dtypes.bfloat16)
        else:
            # layer outputs stay O(1): rms 0.4 / sqrt(fan-in)
            scale = 0.4 / np.sqrt(fill.get("fan_in") or hidden)
            tensors[name] = _noise_bf16(
                rng, shape, scale * float(fill.get("gain", 1.0)))
    fname = f"model-{index:05d}.safetensors"
    save_file(tensors, os.path.join(path, fname))
    return fname, list(tensors), sum(t.nbytes for t in tensors.values())


def write_tokenizer(path: str, vocab_size: int) -> None:
    """WordLevel over whitespace, one fixed-width word per id (with names
    of varying width `w1` would match inside `w17`)."""
    vocab = {WORD.format(i): i for i in range(vocab_size)}
    tok = {"version": "1.0", "truncation": None, "padding": None,
           "added_tokens": [], "normalizer": None,
           "pre_tokenizer": {"type": "WhitespaceSplit"},
           "post_processor": None, "decoder": None,
           "model": {"type": "WordLevel", "vocab": vocab,
                     "unk_token": WORD.format(0)}}
    with open(os.path.join(path, "tokenizer.json"), "w") as f:
        json.dump(tok, f)
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "clean_up_tokenization_spaces": False,
                   "model_max_length": 1 << 20}, f)


def head_gain_of(config: dict) -> float:
    return float((config.get("weights") or {}).get("head_gain", 16.0))


def _plan(config: dict) -> tuple:
    """(model keys, family module, fills by kind) of a configuration."""
    hf = hf_config(config)
    fam = family.load("families", config)
    return hf, fam, fills_of(fam, hf, head_gain_of(config))


def marker_of(config: dict, seed: int) -> str:
    """What a checkpoint directory holds: the model's keys, the seed, the
    head's gain and, where the family brings fills of its own, those."""
    hf, _, fills = _plan(config)
    own = {k: v for k, v in fills.items() if k not in BASE_KINDS}
    what = [hf, seed, head_gain_of(config), "v1"] + ([own] if own else [])
    return hashlib.sha256(json.dumps(what, sort_keys=True).encode()
                          ).hexdigest()


def write_checkpoint(path: str, config: dict, seed: int) -> bool:
    """config.json + sharded safetensors + index + tokenizer under `path`.
    A directory whose marker matches (configuration, seed) is reused;
    anything else there is replaced, so one directory never holds more
    than one checkpoint. Returns True when it wrote."""
    want = marker_of(config, seed)
    hf, fam, fills = _plan(config)
    marker = os.path.join(path, ".bench_ckpt")
    try:
        with open(marker) as f:
            if f.read() == want:
                return False
    except OSError:
        pass
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f, indent=1)
    write_tokenizer(path, hf["vocab_size"])
    shards = _plan_shards(fam.tensor_specs(hf))
    weight_map, total = {}, 0
    with concurrent.futures.ThreadPoolExecutor(WRITER_THREADS) as pool:
        futs = [pool.submit(_write_shard, path, i, specs, seed,
                            hf["hidden_size"], fills)
                for i, specs in enumerate(shards)]
        for fut in futs:
            fname, names, nbytes = fut.result()
            weight_map.update(dict.fromkeys(names, fname))
            total += nbytes
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": total},
                   "weight_map": weight_map}, f)
    with open(marker, "w") as f:
        f.write(want)
    return True
