"""A toy SDAR-MoE checkpoint written by the benchmark's own writer
(`benchmarks/chip/lib/ckpt.py` + `families/sdar_moe.py`), the program's
loader and engine over it, and the benchmark's plain reference
(`benchmarks/chip/reference/sdar_moe.py`) imported as it stands. Shared by
the SDAR test files; no test of its own."""

import asyncio
import copy
import os
import sys

import numpy as np

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

BLOCK = 4
MASK_ID = 299
CONFIG = {
    "family": "sdar_moe", "rehearsal": True,
    "architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
    "attention_bias": False, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "norm_topk_prob": True,
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 300,
    "mask_token_id": MASK_ID, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "weights": {"head_gain": 16.0},
    "deployment": {
        "chips": 1, "tensor_parallel": 1,
        "worker_flags": {"dllm-block-length": BLOCK,
                         "dllm-denoising-steps": 4,
                         "dllm-unmasking-strategy": "sequential"},
        "weight_bytes": {"layers": 2, "lm_head": 2, "kv": 2}},
}


def config_for(steps=4, strategy="sequential", **model_keys) -> dict:
    cfg = copy.deepcopy(CONFIG)
    cfg.update(model_keys)
    cfg["deployment"]["worker_flags"].update({
        "dllm-denoising-steps": steps,
        "dllm-unmasking-strategy": strategy})
    return cfg


def write_checkpoint(path: str, seed: int = 11) -> None:
    from lib import ckpt

    ckpt.write_checkpoint(path, CONFIG, seed)


def reader(path: str):
    from lib import refio

    return refio.Checkpoint(path)


def reference_logits(read, config, sequences, starts):
    from lib import family, refio

    ref = family.load("reference", config)
    return ref.logits(read, config, sequences, starts,
                      refio.bits_of(config, None))


def log_softmax(z):
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def request(prompt, max_tokens, temperature=0.0, seed=None, **sampling):
    return {"token_ids": list(prompt),
            "sampling": {"temperature": temperature, "seed": seed,
                         **sampling},
            "stop": {"max_tokens": max_tokens, "ignore_eos": True}}


async def collect(engine, req):
    """(tokens, log-probabilities, frames, finish reason, error)."""
    from dynamo_tpu.runtime.context import Context

    toks, lps, frames, finish, error = [], [], 0, None, None
    async for out in engine.generate(req, Context()):
        toks += out.get("token_ids") or []
        lps += out.get("log_probs") or []
        frames += bool(out.get("token_ids"))
        if out.get("finish_reason"):
            finish = out["finish_reason"]
            error = (out.get("extra") or {}).get("error")
    return toks, lps, frames, finish, error


def serve(engine_config, params, requests):
    """Serve `requests` together on a fresh engine; returns
    ([collect() results], pages still active after the last one)."""
    from dynamo_tpu.engine.engine import TpuEngine

    async def run():
        engine = TpuEngine(engine_config, params=params)
        try:
            got = await asyncio.gather(*(collect(engine, r)
                                         for r in requests))
            return got, engine.pool.active_pages
        finally:
            await engine.close()

    return asyncio.run(run())
