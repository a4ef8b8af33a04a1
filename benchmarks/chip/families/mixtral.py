"""The Mixtral family (the llama family's attention, the feed-forward block
replaced by a router and sparse experts, the top k of them a token): the
tensors of its checkpoint in the `block_sparse_moe` layout, the fills of its
own, and the bytes its decode step must read. Plain Python.
"""

from __future__ import annotations


def fills(hf: dict) -> dict:
    """The router reads the residual stream like any dense projection, but
    its scores must be uneven, or every token's top k is a near tie and the
    bf16 program and the float32 reference route differently: noise x 16."""
    return {"router": {"fill": "noise", "gain": 16.0}}


def tensor_specs(hf: dict) -> list[tuple[str, tuple, str]]:
    hidden, inter = hf["hidden_size"], hf["intermediate_size"]
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    head_dim = hf.get("head_dim") or hidden // heads
    vocab, experts = hf["vocab_size"], hf["num_local_experts"]
    out = [("model.embed_tokens.weight", (vocab, hidden), "dense")]
    for i in range(hf["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hidden,), "norm"))
        for proj, rows in (("q", heads), ("k", kv_heads), ("v", kv_heads)):
            out.append((p + f"self_attn.{proj}_proj.weight",
                        (rows * head_dim, hidden), "dense"))
        out.append((p + "self_attn.o_proj.weight",
                    (hidden, heads * head_dim), "dense"))
        out.append((p + "post_attention_layernorm.weight", (hidden,),
                    "norm"))
        moe = p + "block_sparse_moe."
        out.append((moe + "gate.weight", (experts, hidden), "router"))
        for e in range(experts):
            x = moe + f"experts.{e}."
            out.append((x + "w1.weight", (inter, hidden), "dense"))
            out.append((x + "w2.weight", (hidden, inter), "dense"))
            out.append((x + "w3.weight", (inter, hidden), "dense"))
    out.append(("model.norm.weight", (hidden,), "norm"))
    if not hf.get("tie_word_embeddings"):
        out.append(("lm_head.weight", (vocab, hidden), "head"))
    return out


def decode_step_bytes(config: dict, kv_tokens: float, lanes: float) -> float:
    """Attention weights, router and head once; of the experts, those that
    some lane's token chose: with `lanes` tokens each choosing k of X at
    random, X x (1 - (1 - k / X) ** lanes) experts are read. Keys and values
    of every token in the lanes' contexts."""
    h, inter = config["hidden_size"], config["intermediate_size"]
    heads = config["num_attention_heads"]
    kvh = config.get("num_key_value_heads", heads)
    d = config.get("head_dim") or h // heads
    layers = config["num_hidden_layers"]
    x, k = config["num_local_experts"], config.get("num_experts_per_tok", 2)
    wb = config["deployment"]["weight_bytes"]
    hit = x * (1.0 - (1.0 - k / x) ** max(lanes, 1.0))
    per_layer = (h * (heads + 2 * kvh) * d + heads * d * h
                 + hit * 3 * h * inter) * wb["layers"] + x * h * 2
    weights = layers * per_layer + h * config["vocab_size"] * wb["lm_head"]
    kv = kv_tokens * 2 * layers * kvh * d * wb["kv"]
    return weights + kv
