"""The llama family (Llama, Mistral, Qwen2: dense GQA, SwiGLU, RMSNorm,
rotary positions): the tensors of its checkpoint and the bytes its decode
step must read. Plain Python: the benchmark's parent imports this.

Only what the algorithm cannot avoid is counted: scales, norms, the
embedding rows of the step's tokens and every activation are left out, so a
roofline share read from these is a little low, never above what the chip
did.
"""

from __future__ import annotations


def tensor_specs(hf: dict) -> list[tuple[str, tuple, str]]:
    """(name, shape, kind) of every tensor of a llama-family checkpoint;
    kind is `norm`, `head` or `dense`."""
    hidden, inter = hf["hidden_size"], hf["intermediate_size"]
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    head_dim = hf.get("head_dim") or hidden // heads
    vocab = hf["vocab_size"]
    arch = (hf.get("architectures") or [""])[0].lower()
    biased = bool(hf.get("attention_bias", "qwen2" in arch))
    out = [("model.embed_tokens.weight", (vocab, hidden), "dense")]
    for i in range(hf["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hidden,), "norm"))
        for proj, rows in (("q", heads), ("k", kv_heads), ("v", kv_heads)):
            out.append((p + f"self_attn.{proj}_proj.weight",
                        (rows * head_dim, hidden), "dense"))
            if biased:
                out.append((p + f"self_attn.{proj}_proj.bias",
                            (rows * head_dim,), "dense"))
        out.append((p + "self_attn.o_proj.weight",
                    (hidden, heads * head_dim), "dense"))
        out.append((p + "post_attention_layernorm.weight", (hidden,),
                    "norm"))
        out.append((p + "mlp.gate_proj.weight", (inter, hidden), "dense"))
        out.append((p + "mlp.up_proj.weight", (inter, hidden), "dense"))
        out.append((p + "mlp.down_proj.weight", (hidden, inter), "dense"))
    out.append(("model.norm.weight", (hidden,), "norm"))
    if not hf.get("tie_word_embeddings"):
        out.append(("lm_head.weight", (vocab, hidden), "head"))
    return out


def decode_step_bytes(config: dict, kv_tokens: float, lanes: float) -> float:
    """One decode step reads every layer's weights and the output head once
    (at the width they are served in, `deployment.weight_bytes`), and the
    keys and values of every token in the lanes' contexts. Dense weights are
    read whatever the number of lanes, so `lanes` is unused here."""
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
