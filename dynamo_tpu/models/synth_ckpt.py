"""Materialize a synthetic HF-format Llama checkpoint on disk.

Purpose: the bench / tests need to drive the REAL checkpoint path —
`resolve_model` → `config_from_hf` → sharded-safetensors index →
`load_llama_params` → host int8 quantize → device placement — at
realistic scale (Llama-3-8B-class). This image ships no pretrained
checkpoints and has no network egress, so the weights themselves are
synthetic noise; everything else (file format, sharding, index json,
dtypes, load path, memory budget, transfer cost) is exactly what a real
checkpoint exercises. Reference analog: the recipes' model stanzas
(`/root/reference/recipes/llama-3-70b/`) assume HF-layout checkpoints.

Weights are drawn from a shared bf16 noise pool with per-tensor offsets
and scale — pool slicing runs at memcpy speed (a 1-core host generates
16 GB in ~2 min instead of ~5), while values stay N(0, scale)-ish so
norms/softmaxes behave.
"""

from __future__ import annotations

import json
import os

import numpy as np

PRESETS = {
    # name: (hidden, intermediate, layers, heads, kv_heads, vocab)
    "llama3-8b": (4096, 14336, 32, 32, 8, 128256),
    "llama3-3b": (3072, 8192, 28, 24, 8, 128256),
    "llama2-1b": (2048, 8192, 16, 16, 8, 32000),
    "tiny": (64, 128, 2, 4, 2, 300),
    # Qwen2 family: same geometry class but q/k/v projections carry
    # biases (arch "Qwen2ForCausalLM" → loader sets attention_bias)
    "qwen2-tiny": (64, 128, 2, 4, 2, 300),
    "qwen2-1b": (2048, 8192, 16, 16, 8, 32000),
    # Mixtral family: block_sparse_moe router + per-expert w1/w2/w3
    # (arch "MixtralForCausalLM" → loader returns MoeConfig)
    "mixtral-tiny": (64, 96, 2, 4, 2, 300),
}

# MoE presets: name -> (num_local_experts, num_experts_per_tok)
MOE_PRESETS = {"mixtral-tiny": (4, 2)}

_POOL_ELEMS = 1 << 24        # 16M bf16 = 32 MB shared noise pool


def _pool(seed: int, scale: float):
    """Noise pool PRE-SCALED to the dense-weight scale, so tensor fill
    below is a pure bf16 memcpy (no per-element convert over 16 GB)."""
    import ml_dtypes

    rng = np.random.default_rng(seed)
    return (rng.standard_normal(_POOL_ELEMS, dtype=np.float32) * scale) \
        .astype(ml_dtypes.bfloat16)


def _fill(pool, offset: int, shape):
    """Cyclic copy out of the pool with direct slice assignments —
    one write per output byte (a strided-view reshape would silently
    materialize an intermediate copy and double the 16 GB of traffic
    this trick exists to avoid)."""
    n = int(np.prod(shape))
    out = np.empty(n, dtype=pool.dtype)
    first = min(n, _POOL_ELEMS - offset)
    out[:first] = pool[offset:offset + first]
    pos = first
    while pos < n:
        m = min(_POOL_ELEMS, n - pos)
        out[pos:pos + m] = pool[:m]
        pos += m
    return out.reshape(shape)


def write_synthetic_hf_checkpoint(path: str, preset: str = "llama3-8b",
                                  seed: int = 0,
                                  shard_bytes: int = 2 << 30,
                                  layers: int = 0,
                                  head_gain: float = 1.0) -> str:
    """Write config.json + sharded safetensors + index under `path`.

    Returns `path`. Idempotent: a directory whose marker file matches
    the preset is reused as-is (the 8B build writes 16 GB). `layers`
    cuts the preset's depth (0 = as published); widths never change.
    `head_gain` scales lm_head: at 1.0 the logits of noise weights are
    nearly flat (std ~0.4, every token a near-tie), which makes token
    comparisons between two runs meaningless; a gain of 16 gives a
    peaked distribution whose greedy choice survives bf16 rounding."""
    from safetensors.numpy import save_file

    hidden, inter, full_depth, heads, kv_heads, vocab = PRESETS[preset]
    layers = layers or full_depth
    marker = os.path.join(path, ".synth_ckpt")
    want = f"{preset}:{seed}:v1" + (
        f":L{layers}" if layers != full_depth else "") + (
        f":g{head_gain:g}" if head_gain != 1.0 else "")
    if os.path.exists(marker) and open(marker).read() == want:
        return path
    head_dim = hidden // heads
    qwen = preset.startswith("qwen2")
    moe = MOE_PRESETS.get(preset)
    os.makedirs(path, exist_ok=True)
    if moe:
        arch, model_type = "MixtralForCausalLM", "mixtral"
    elif qwen:
        arch, model_type = "Qwen2ForCausalLM", "qwen2"
    else:
        arch, model_type = "LlamaForCausalLM", "llama"
    cfg = {
        "architectures": [arch],
        "model_type": model_type,
        "hidden_size": hidden, "intermediate_size": inter,
        "num_hidden_layers": layers, "num_attention_heads": heads,
        "num_key_value_heads": kv_heads, "head_dim": head_dim,
        "vocab_size": vocab, "rms_norm_eps": 1e-5,
        "rope_theta": 500000.0, "max_position_embeddings": 131072,
        "bos_token_id": 1, "eos_token_id": 2,
        "tie_word_embeddings": False, "dtype": "bfloat16",
    }
    if moe:
        cfg["num_local_experts"], cfg["num_experts_per_tok"] = moe
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f, indent=1)

    scale = 0.4 / np.sqrt(hidden)      # keeps layer outputs O(1)
    pool = _pool(seed, scale)
    rng = np.random.default_rng(seed + 1)

    def tensors():
        yield "model.embed_tokens.weight", (vocab, hidden)
        for i in range(layers):
            p = f"model.layers.{i}."
            yield p + "input_layernorm.weight", (hidden,)
            yield p + "self_attn.q_proj.weight", \
                (heads * head_dim, hidden)
            yield p + "self_attn.k_proj.weight", \
                (kv_heads * head_dim, hidden)
            yield p + "self_attn.v_proj.weight", \
                (kv_heads * head_dim, hidden)
            if qwen:
                yield p + "self_attn.q_proj.bias", (heads * head_dim,)
                yield p + "self_attn.k_proj.bias", \
                    (kv_heads * head_dim,)
                yield p + "self_attn.v_proj.bias", \
                    (kv_heads * head_dim,)
            yield p + "self_attn.o_proj.weight", \
                (hidden, heads * head_dim)
            yield p + "post_attention_layernorm.weight", (hidden,)
            if moe:
                n_exp = moe[0]
                yield p + "block_sparse_moe.gate.weight", \
                    (n_exp, hidden)
                for e in range(n_exp):
                    ep = p + f"block_sparse_moe.experts.{e}."
                    yield ep + "w1.weight", (inter, hidden)
                    yield ep + "w3.weight", (inter, hidden)
                    yield ep + "w2.weight", (hidden, inter)
            else:
                yield p + "mlp.gate_proj.weight", (inter, hidden)
                yield p + "mlp.up_proj.weight", (inter, hidden)
                yield p + "mlp.down_proj.weight", (hidden, inter)
        yield "model.norm.weight", (hidden,)
        yield "lm_head.weight", (vocab, hidden)

    shard, shard_n, shard_id, weight_map, sizes = {}, 0, 0, {}, []

    def flush():
        nonlocal shard, shard_n, shard_id
        if not shard:
            return
        name = f"model-{shard_id:05d}.safetensors"
        save_file(shard, os.path.join(path, name))
        for k in shard:
            weight_map[k] = name
        sizes.append(shard_n)
        shard, shard_n = {}, 0
        shard_id += 1

    for name, shape in tensors():
        # norms must be ~1.0 (RMSNorm gains), not noise — match by NAME:
        # qwen bias vectors can share the (hidden,) shape
        if name.endswith("norm.weight"):
            t = np.ones(shape, dtype=pool.dtype)
        else:
            off = int(rng.integers(0, _POOL_ELEMS))
            t = _fill(pool, off, shape)
            if name == "lm_head.weight" and head_gain != 1.0:
                t = (t.astype(np.float32) * head_gain).astype(pool.dtype)
        shard[name] = t
        shard_n += t.nbytes
        if shard_n >= shard_bytes:
            flush()
    flush()
    index = {"metadata": {"total_size": int(sum(sizes))},
             "weight_map": weight_map}
    with open(os.path.join(path, "model.safetensors.index.json"),
              "w") as f:
        json.dump(index, f)
    with open(marker, "w") as f:
        f.write(want)
    return path
