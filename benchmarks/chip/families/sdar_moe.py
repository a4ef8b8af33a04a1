"""The SDAR-MoE family (`model_type: sdar_moe`; the Qwen3-MoE block served
by diffusion over blocks): the tensors of its checkpoint in the Qwen3-MoE
layout (`mlp.gate`, `mlp.experts.{e}.{gate,up,down}_proj`, per-head
`self_attn.{q,k}_norm`), the fills of its own, and the bytes a decode step
and one forward's grouped products must read. Plain Python.

A step is one token a lane. A block of B tokens costs T denoising forwards
and one that commits it (`deployment.worker_flags`: `dllm-block-length`,
`dllm-denoising-steps`), so a step is (T + 1) / B forwards. Only what the
algorithm cannot avoid is counted, as in `families/llama.py`.
"""

from __future__ import annotations


def fills(hf: dict) -> dict:
    """The router's scores must be uneven, or the 8th and 9th expert of a
    token are a near tie and the bf16 program and the float32 reference
    route differently: noise x 16, as `families/mixtral.py`."""
    return {"router": {"fill": "noise", "gain": 16.0}}


def _sizes(hf: dict) -> tuple:
    hidden = hf["hidden_size"]
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    head_dim = hf.get("head_dim") or hidden // heads
    return (hidden, hf["moe_intermediate_size"], heads, kv_heads, head_dim,
            hf["num_experts"], hf["num_experts_per_tok"])


def tensor_specs(hf: dict) -> list[tuple[str, tuple, str]]:
    hidden, inter, heads, kv_heads, head_dim, experts, _ = _sizes(hf)
    vocab = hf["vocab_size"]
    out = [("model.embed_tokens.weight", (vocab, hidden), "dense")]
    for i in range(hf["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hidden,), "norm"))
        for proj, rows in (("q", heads), ("k", kv_heads), ("v", kv_heads)):
            out.append((p + f"self_attn.{proj}_proj.weight",
                        (rows * head_dim, hidden), "dense"))
        out.append((p + "self_attn.o_proj.weight",
                    (hidden, heads * head_dim), "dense"))
        out.append((p + "self_attn.q_norm.weight", (head_dim,), "norm"))
        out.append((p + "self_attn.k_norm.weight", (head_dim,), "norm"))
        out.append((p + "post_attention_layernorm.weight", (hidden,),
                    "norm"))
        out.append((p + "mlp.gate.weight", (experts, hidden), "router"))
        for e in range(experts):
            x = p + f"mlp.experts.{e}."
            out.append((x + "gate_proj.weight", (inter, hidden), "dense"))
            out.append((x + "up_proj.weight", (inter, hidden), "dense"))
            out.append((x + "down_proj.weight", (hidden, inter), "dense"))
    out.append(("model.norm.weight", (hidden,), "norm"))
    if not hf.get("tie_word_embeddings"):
        out.append(("lm_head.weight", (vocab, hidden), "head"))
    return out


def block_steps(config: dict) -> tuple[int, int]:
    """(block length B, denoising steps T) the deployment serves with."""
    flags = config["deployment"]["worker_flags"]
    block = int(flags["dllm-block-length"])
    return block, int(flags.get("dllm-denoising-steps") or block)


def experts_hit(config: dict, rows: float) -> float:
    """Experts some row chose: `rows` tokens each choosing k of X at
    random reach X x (1 - (1 - k / X) ** rows) of them."""
    x, k = config["num_experts"], config["num_experts_per_tok"]
    return x * (1.0 - (1.0 - k / x) ** max(rows, 1.0))


def moe_forward_bytes(config: dict, rows: float) -> float:
    """What the grouped products of ONE forward over `rows` token rows
    must move, all layers: the three projections of every expert some row
    chose, as served, and the routed rows in and out of them (a row into
    gate and up once, their two results out, the product into down, its
    result out; activations 2 bytes)."""
    h, inter, _, _, _, _, k = _sizes(config)
    wb = config["deployment"]["weight_bytes"]
    weights = experts_hit(config, rows) * 3 * h * inter * wb["layers"]
    moved = rows * k * (2 * h + 3 * inter) * 2
    return config["num_hidden_layers"] * (weights + moved)


def decode_step_bytes(config: dict, kv_tokens: float, lanes: float) -> float:
    """One step, a token a lane, is (T + 1) / B forwards over lanes x B
    rows. Each forward reads the attention weights and the router once,
    the experts its rows reach, and the keys and values of the lanes'
    contexts with the block's own rows; the T denoising forwards read the
    output head too (the committing forward needs no logits and runs
    none)."""
    h, _, heads, kvh, d, x, _ = _sizes(config)
    layers = config["num_hidden_layers"]
    wb = config["deployment"]["weight_bytes"]
    block, steps = block_steps(config)
    rows = max(lanes, 1.0) * block
    attention = layers * ((h * (heads + 2 * kvh) * d + heads * d * h)
                          * wb["layers"] + x * h * 2)
    experts = layers * experts_hit(config, rows) * 3 * h \
        * config["moe_intermediate_size"] * wb["layers"]
    kv = (kv_tokens + rows) * 2 * layers * kvh * d * wb["kv"]
    head = h * config["vocab_size"] * wb["lm_head"]
    return ((steps + 1) * (attention + experts + kv) + steps * head) / block
