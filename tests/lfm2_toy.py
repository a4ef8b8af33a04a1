"""A toy LFM2-MoE checkpoint written by the benchmark's own writer
(`benchmarks/chip/lib/ckpt.py` + `families/lfm2_moe.py`), the program's
loader over it, and the benchmark's plain reference
(`benchmarks/chip/reference/lfm2_moe.py`) imported as it stands. Shared by
the LFM2 test files; no test of its own."""

import copy

from tests.sdar_toy import (collect, log_softmax, reader,  # noqa: F401
                            reference_logits, request, serve)

TYPES = ["conv", "conv", "full_attention", "conv", "full_attention", "conv"]
CONFIG = {
    "family": "lfm2_moe", "rehearsal": True,
    "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe",
    "layer_types": TYPES, "num_hidden_layers": len(TYPES),
    "num_dense_layers": 2, "hidden_size": 256, "vocab_size": 300,
    "norm_eps": 1e-05, "conv_L_cache": 3, "conv_bias": False,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 160, "moe_intermediate_size": 96,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "rope_theta": 1000000, "tie_word_embeddings": True,
    "weights": {"head_gain": 6.0},
    "deployment": {
        "chips": 1, "tensor_parallel": 1, "worker_flags": {},
        "weight_bytes": {"layers": 2, "lm_head": 2, "kv": 2}},
}


def config_for(layers_bytes: int = 2, **model_keys) -> dict:
    """The toy's configuration with its projections stated in bf16 (2) or
    int8 (1): what the reference takes them at."""
    cfg = copy.deepcopy(CONFIG)
    cfg.update(model_keys)
    cfg["deployment"]["weight_bytes"].update(layers=layers_bytes,
                                             lm_head=layers_bytes)
    return cfg


def write_checkpoint(path: str, seed: int = 11) -> None:
    from lib import ckpt

    ckpt.write_checkpoint(path, CONFIG, seed)
