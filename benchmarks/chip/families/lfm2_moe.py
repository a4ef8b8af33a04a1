"""The LFM2-MoE family (`model_type: lfm2_moe`): a layer is an operator and
an FFN. `layer_types[i]` names the operator, a gated short convolution
(`conv`) or GQA attention with per-head q/k norm (`full_attention`); the
first `num_dense_layers` FFNs are dense SwiGLU, the rest sigmoid-routed
SwiGLU experts with a bias on the choice. This file lists the tensors of its
checkpoint (`model.layers.{i}.{conv,self_attn,feed_forward}.*`, the head
tied to the embedding), the fills of its own, and the bytes a decode step,
its grouped products and its decode attention must move. Plain Python. Only
what the algorithm cannot avoid is counted, as in `families/llama.py`.
"""

from __future__ import annotations


def fills(hf: dict) -> dict:
    """Weights that can fail the program. The router's scores must be
    uneven but not saturated: noise x 4, as `families/nemotron_h.py`. With
    32 experts the 4th and 5th sigmoid score of a token lie ~0.02 apart;
    the bias is +-[0.016, 0.031), so it changes some token's 4th expert in
    every sequence and weighting by the biased score fails.

    The head is the embedding, so a token's own embedding is in the
    residual that meets the head: its logit leads the others' spread by
    sqrt(hidden) x e / rms(x), e the embedding's rms. With the writer's
    plain fills the layers add ~0.1 each (a product of two 0.4s through a
    projection of 0.4) and every stream settles on one id. So what a
    sublayer adds is held at ~0.5 of rms each: the projections whose
    outputs are multiplied with each other (`in_proj`, w1, w3) and v x 2.5
    (rms 1), the projections back into the residual at their own fan-in,
    x 3 after attention's average, x 2 after a dense FFN. 26 such
    sublayers put rms(x) at ~3 and a token's own logit ~2 sigma up: a
    candidate, not the winner. The experts' w2 is x 0.5 at its own fan-in,
    an eighth of what the others add: a token's 4th and 5th expert lie
    ~0.15 sigma of a router logit apart, bf16 and float32 routing swap
    them in about one layer of 22 a token, and a swap moves the residual
    by 0.7 of what the layer adds, which the layers above amplify. At x 2.5
    the sound program read logprob_dev 14.2 on the chip, as far from the
    reference as its int4 control (28.5); the same 24 layers at a width of
    256 on the CPU read a largest deviation of 14.1 / 7.2 / 2.2 / 1.2 over
    200 tokens at x 2.5 / 1 / 0.5 / 0.25 against the control's 20 / 11 /
    9.7 / 8.3 (my runs, PR 48). The taps
    are +-[0.5, 1), all three of one order, so two thirds of what a conv
    layer adds comes out of the slot's state: a state dropped at a chunk
    boundary, taken at the padded end, or left by the slot's last tenant
    fails `correct`."""
    return {"router": {"fill": "noise", "gain": 4.0},
            "router_bias": {"fill": "noise", "gain": 0.06, "fan_in": 1},
            "wide": {"fill": "noise", "gain": 2.5},
            "taps": {"fill": "noise", "gain": 4.0, "fan_in": 4},
            "attn_out": {"fill": "noise", "gain": 3.0},
            "dense_out": {"fill": "noise", "gain": 2.0,
                          "fan_in": hf["intermediate_size"]},
            "expert_out": {"fill": "noise", "gain": 0.5,
                           "fan_in": hf["moe_intermediate_size"]}}


def operators(hf: dict) -> list:
    return list(hf["layer_types"])[:hf["num_hidden_layers"]]


def count(hf: dict, kind: str) -> int:
    """Layers of a kind: `conv`, `full_attention`, `dense` or `moe`."""
    dense = min(hf["num_dense_layers"], hf["num_hidden_layers"])
    if kind in ("dense", "moe"):
        return dense if kind == "dense" else hf["num_hidden_layers"] - dense
    return sum(1 for t in operators(hf) if t == kind)


def _sizes(hf: dict) -> tuple:
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    return (hidden, heads, hf.get("num_key_value_heads", heads),
            hf.get("head_dim") or hidden // heads)


def tensor_specs(hf: dict) -> list[tuple[str, tuple, str]]:
    hidden, heads, kv_heads, head_dim = _sizes(hf)
    vocab, taps = hf["vocab_size"], hf["conv_L_cache"]
    dense, width = hf["intermediate_size"], hf["moe_intermediate_size"]
    experts = hf["num_experts"]
    # the head is the embedding (tied): it carries the head's gain
    out = [("model.embed_tokens.weight", (vocab, hidden), "head")]
    for i, op in enumerate(operators(hf)):
        p = f"model.layers.{i}."
        out.append((p + "operator_norm.weight", (hidden,), "norm"))
        if op == "conv":
            out += [(p + "conv.in_proj.weight", (3 * hidden, hidden),
                     "wide"),
                    (p + "conv.conv.weight", (hidden, 1, taps), "taps"),
                    (p + "conv.out_proj.weight", (hidden, hidden), "dense")]
        else:
            a = p + "self_attn."
            out += [(a + "q_proj.weight", (heads * head_dim, hidden),
                     "dense"),
                    (a + "k_proj.weight", (kv_heads * head_dim, hidden),
                     "dense"),
                    (a + "v_proj.weight", (kv_heads * head_dim, hidden),
                     "wide"),
                    (a + "out_proj.weight", (hidden, heads * head_dim),
                     "attn_out"),
                    (a + "q_layernorm.weight", (head_dim,), "norm"),
                    (a + "k_layernorm.weight", (head_dim,), "norm")]
        out.append((p + "ffn_norm.weight", (hidden,), "norm"))
        f = p + "feed_forward."
        if i < hf["num_dense_layers"]:
            out += [(f + "w1.weight", (dense, hidden), "wide"),
                    (f + "w3.weight", (dense, hidden), "wide"),
                    (f + "w2.weight", (hidden, dense), "dense_out")]
        else:
            out += [(f + "gate.weight", (experts, hidden), "router"),
                    (f + "expert_bias", (experts,), "router_bias")]
            for e in range(experts):
                x = f + f"experts.{e}."
                out += [(x + "w1.weight", (width, hidden), "wide"),
                        (x + "w3.weight", (width, hidden), "wide"),
                        (x + "w2.weight", (hidden, width), "expert_out")]
    out.append(("model.embedding_norm.weight", (hidden,), "norm"))
    return out


def block_steps(config: dict) -> tuple[int, int]:
    """(1, 0): a step is one token a lane and one forward
    (`readers/op_hbm_roofline.py` asks)."""
    return 1, 0


def layer_params(hf: dict) -> dict:
    """Parameters of one operator or FFN of each kind and of one routed
    expert, at the published widths (norms with their layer)."""
    hidden, heads, kv_heads, head_dim = _sizes(hf)
    q, kv = heads * head_dim, kv_heads * head_dim
    expert = 3 * hidden * hf["moe_intermediate_size"]
    return {
        "conv": hidden * 3 * hidden + hidden * hidden
        + hidden * hf["conv_L_cache"] + hidden,
        "full_attention": hidden * (q + 2 * kv) + q * hidden
        + 2 * head_dim + hidden,
        "dense": 3 * hidden * hf["intermediate_size"] + hidden,
        "expert": expert,
        "moe": hf["num_experts"] * (expert + hidden + 1) + hidden,
        "embed": hf["vocab_size"] * hidden}


def parameters(hf: dict) -> int:
    """Every parameter of the model, the tied head counted once."""
    per = layer_params(hf)
    return sum(count(hf, kind) * per[kind] for kind in (
        "conv", "full_attention", "dense", "moe")) + per["embed"] \
        + hf["hidden_size"]


def kv_token_bytes(config: dict) -> int:
    """Bytes of keys and values a token of context holds, all attention
    layers."""
    _, _, kv_heads, head_dim = _sizes(config)
    return 2 * count(config, "full_attention") * kv_heads * head_dim \
        * config["deployment"]["weight_bytes"]["kv"]


def state_bytes(config: dict) -> int:
    """Bytes a sequence's slot holds, all conv layers: the inputs of the
    convolution's older taps, `conv_L_cache - 1` of them, in bf16."""
    return count(config, "conv") * (config["conv_L_cache"] - 1) \
        * config["hidden_size"] * 2


def experts_hit(config: dict, rows: float) -> float:
    """Experts some row chose: `rows` tokens each choosing k of X at
    random reach X x (1 - (1 - k / X) ** rows) of them."""
    x, k = config["num_experts"], config["num_experts_per_tok"]
    return x * (1.0 - (1.0 - k / x) ** max(rows, 1.0))


def moe_forward_bytes(config: dict, rows: float) -> float:
    """What the grouped products of ONE forward over `rows` token rows
    must move, all expert layers: the three int8 projections of every
    expert the rows x 4 draws reach, as served, and the routed rows in and
    out of them (a row into gate and up once, their two results out, the
    product into down, its result out; activations 2 bytes)."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    wb = config["deployment"]["weight_bytes"]
    weights = experts_hit(config, rows) * 3 * h * f * wb["layers"]
    moved = rows * config["num_experts_per_tok"] * (2 * h + 3 * f) * 2
    return count(config, "moe") * (weights + moved)


def attn_forward_bytes(config: dict, kv_tokens: float, rows: float) -> float:
    """What the decode attention of ONE forward must move, all attention
    layers: the keys and values of `kv_tokens` of live context at the
    model's own 64-wide heads, and each lane's q in and output out
    (2 bytes)."""
    _, heads, _, head_dim = _sizes(config)
    moved = rows * 2 * heads * head_dim * 2
    return kv_tokens * kv_token_bytes(config) \
        + count(config, "full_attention") * moved


def decode_step_bytes(config: dict, kv_tokens: float, lanes: float) -> float:
    """One step, a token a lane: every conv and attention operator, every
    dense FFN and every router once, the routed experts the lanes' draws
    reach, the output head, the keys and values of `kv_tokens` of context
    in the attention layers, and `lanes` x the convolutions' state, read
    and written."""
    per = layer_params(config)
    wb = config["deployment"]["weight_bytes"]
    lanes = max(lanes, 1.0)
    h = config["hidden_size"]
    dense = sum(count(config, kind) * per[kind] for kind in (
        "conv", "full_attention", "dense")) * wb["layers"]
    routers = count(config, "moe") * config["num_experts"] * h * 2
    experts = count(config, "moe") * experts_hit(config, lanes) \
        * per["expert"] * wb["layers"]
    head = h * config["vocab_size"] * wb["lm_head"]
    state = lanes * 2 * state_bytes(config)
    return dense + routers + experts + head \
        + kv_tokens * kv_token_bytes(config) + state
