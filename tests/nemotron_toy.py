"""A toy Nemotron-H checkpoint written by the benchmark's own writer
(`benchmarks/chip/lib/ckpt.py` + `families/nemotron_h.py`), the program's
loader over it, and the benchmark's plain reference
(`benchmarks/chip/reference/nemotron_h.py`) imported as it stands. Shared by
the Nemotron test files; no test of its own."""

import copy

from tests.sdar_toy import (collect, log_softmax, reader,  # noqa: F401
                            reference_logits, request, serve)

PATTERN = "MEM*EM"
CONFIG = {
    "family": "nemotron_h", "rehearsal": True,
    "architectures": ["NemotronHForCausalLM"], "model_type": "nemotron_h",
    "hybrid_override_pattern": PATTERN, "num_hidden_layers": len(PATTERN),
    "hidden_size": 64, "vocab_size": 300, "layer_norm_epsilon": 1e-05,
    "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 96,
    "moe_shared_expert_intermediate_size": 160, "n_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2",
    "tie_word_embeddings": False,
    "weights": {"head_gain": 16.0},
    "deployment": {
        "chips": 1, "tensor_parallel": 1, "worker_flags": {},
        "weight_bytes": {"layers": 2, "lm_head": 2, "kv": 2, "state": 4}},
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
