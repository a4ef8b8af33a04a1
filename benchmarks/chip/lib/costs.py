"""Bytes and operations a step must move, from the configuration alone.

Kept with the benchmark so that no PR that claims a gain can change what a
roofline share is measured against. Only what the algorithm cannot avoid is
counted: scales, norms, the embedding rows of the step's tokens and every
activation are left out, so a share read from these is a little low, never
above what the chip did.
"""

from __future__ import annotations


def decode_step_bytes(config: dict, kv_tokens: float) -> float:
    """One decode step reads every layer's weights and the output head once
    (at the width they are served in, `deployment.weight_bytes`), and the
    keys and values of every token in the lanes' contexts."""
    h, inter = config["hidden_size"], config["intermediate_size"]
    heads = config["num_attention_heads"]
    kvh = config.get("num_key_value_heads", heads)
    d = config.get("head_dim") or h // heads
    layers = config["num_hidden_layers"]
    wb = config["deployment"]["weight_bytes"]
    per_layer = (h * (heads + 2 * kvh) * d + heads * d * h + 3 * h * inter)
    weights = layers * per_layer * wb["layers"] \
        + h * config["vocab_size"] * wb["lm_head"]
    kv = kv_tokens * 2 * layers * kvh * d * wb["kv"]
    return weights + kv
