"""The Nemotron-H family (`model_type: nemotron_h`): a hybrid stack named by
`hybrid_override_pattern` (M a Mamba-2 layer, E an expert layer, * an
attention layer; a layer is ONE mixer), the tensors of its checkpoint
(`backbone.layers.{i}.mixer.*`), the fills of its own, and the bytes a
decode step, its grouped products and its state updates must move. Plain
Python. Only what the algorithm cannot avoid is counted, as in
`families/llama.py`; the expert width is the published one, whatever the
program pads it to.
"""

from __future__ import annotations

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def fills(hf: dict) -> dict:
    """Weights that can fail the program. The router's scores must be
    uneven but not saturated: noise x 4 puts a token's best experts at
    sigmoid scores of 0.9-0.99, a few thousandths apart at the sixth, and
    every expert within reach (x 16, `families/sdar_moe.py`'s, saturates
    the sigmoid and the bias alone would choose). Its bias is +-[0.004,
    0.008): as large as the gap at the sixth expert, so it changes some
    token's sixth expert in every sequence and weighting by the biased
    score fails. `A_log` with fan_in 1 and gain 8 is +-[2, 4):
    A = -exp(A_log) spreads over -[7, 55) and -[0.02, 0.14); `dt_bias` the
    same: softplus gives a step of ~0.02-0.13 or ~2-4. A head with both
    small forgets a few thousandths a token and remembers the whole
    prompt: a state dropped at a chunk boundary, or left by a slot's last
    tenant, fails `correct`. The convolution is +-[0.5, 1) a tap, so that
    x, B and C are O(1) and the state outweighs the D skip (+-[0.5, 1))."""
    return {"router": {"fill": "noise", "gain": 4.0},
            "router_bias": {"fill": "noise", "gain": 0.02, "fan_in": 1},
            "a_log": {"fill": "noise", "gain": 8.0, "fan_in": 1},
            "dt_bias": {"fill": "noise", "gain": 8.0, "fan_in": 1},
            "skip": {"fill": "noise", "gain": 2.5, "fan_in": 1},
            "conv": {"fill": "noise", "gain": 4.0, "fan_in": 4},
            "conv_bias": {"fill": "noise", "gain": 0.25, "fan_in": 1}}


def pattern(hf: dict) -> str:
    return hf["hybrid_override_pattern"][:hf["num_hidden_layers"]]


def count(hf: dict, kind: str) -> int:
    return sum(1 for ch in pattern(hf) if KINDS[ch] == kind)


def _mamba(hf: dict) -> tuple:
    """(inner width, convolution width, in_proj width, heads)."""
    heads = hf["mamba_num_heads"]
    inner = heads * hf["mamba_head_dim"]
    conv = inner + 2 * hf["n_groups"] * hf["ssm_state_size"]
    return inner, conv, inner + conv + heads, heads


def tensor_specs(hf: dict) -> list[tuple[str, tuple, str]]:
    hidden, vocab = hf["hidden_size"], hf["vocab_size"]
    inner, conv, proj, heads = _mamba(hf)
    q = hf["num_attention_heads"] * hf["head_dim"]
    kv = hf["num_key_value_heads"] * hf["head_dim"]
    width, shared = (hf["moe_intermediate_size"],
                     hf["moe_shared_expert_intermediate_size"])
    experts = hf["n_routed_experts"]
    out = [("backbone.embeddings.weight", (vocab, hidden), "dense")]
    for i, ch in enumerate(pattern(hf)):
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        out.append((p + "norm.weight", (hidden,), "norm"))
        if ch == "M":
            out += [(m + "in_proj.weight", (proj, hidden), "dense"),
                    (m + "conv1d.weight", (conv, 1, hf["conv_kernel"]),
                     "conv"),
                    (m + "conv1d.bias", (conv,), "conv_bias"),
                    (m + "dt_bias", (heads,), "dt_bias"),
                    (m + "A_log", (heads,), "a_log"),
                    (m + "D", (heads,), "skip"),
                    (m + "norm.weight", (inner,), "norm"),
                    (m + "out_proj.weight", (hidden, inner), "dense")]
        elif ch == "*":
            out += [(m + "q_proj.weight", (q, hidden), "dense"),
                    (m + "k_proj.weight", (kv, hidden), "dense"),
                    (m + "v_proj.weight", (kv, hidden), "dense"),
                    (m + "o_proj.weight", (hidden, q), "dense")]
        else:
            out += [(m + "gate.weight", (experts, hidden), "router"),
                    (m + "gate.e_score_correction_bias", (experts,),
                     "router_bias"),
                    (m + "shared_experts.up_proj.weight", (shared, hidden),
                     "dense"),
                    (m + "shared_experts.down_proj.weight", (hidden, shared),
                     "dense")]
            for e in range(experts):
                x = m + f"experts.{e}."
                out.append((x + "up_proj.weight", (width, hidden), "dense"))
                out.append((x + "down_proj.weight", (hidden, width),
                            "dense"))
    out.append(("backbone.norm_f.weight", (hidden,), "norm"))
    out.append(("lm_head.weight", (vocab, hidden), "head"))
    return out


def block_steps(config: dict) -> tuple[int, int]:
    """(1, 0): a step is one token a lane and one forward
    (`readers/op_hbm_roofline.py` asks)."""
    return 1, 0


def layer_params(hf: dict) -> dict:
    """Parameters of one layer of each kind, of one routed expert and of
    the shared one, at the published widths."""
    hidden = hf["hidden_size"]
    inner, conv, proj, heads = _mamba(hf)
    q = hf["num_attention_heads"] * hf["head_dim"]
    kv = hf["num_key_value_heads"] * hf["head_dim"]
    expert = 2 * hidden * hf["moe_intermediate_size"]
    shared = 2 * hidden * hf["moe_shared_expert_intermediate_size"]
    return {
        "mamba": hidden * proj + inner * hidden + hidden + inner
        + conv * (hf["conv_kernel"] + 1) + 3 * heads,
        "attn": hidden * (q + 2 * kv) + q * hidden + hidden,
        "expert": expert, "shared": shared,
        "moe": hf["n_routed_experts"] * (expert + hidden + 1) + shared
        + hidden,
        "embed": hf["vocab_size"] * hidden}


def state_bytes(hf: dict, state_itemsize: int = 4) -> tuple[int, int]:
    """(SSM state, convolution tail) bytes a sequence a Mamba layer: the
    state in `deployment.weight_bytes.state` bytes, the tail in bf16."""
    _, conv, _, heads = _mamba(hf)
    return (heads * hf["mamba_head_dim"] * hf["ssm_state_size"]
            * state_itemsize, conv * (hf["conv_kernel"] - 1) * 2)


def experts_hit(config: dict, rows: float) -> float:
    """Experts some row chose: `rows` tokens each choosing k of X at
    random reach X x (1 - (1 - k / X) ** rows) of them."""
    x, k = config["n_routed_experts"], config["num_experts_per_tok"]
    return x * (1.0 - (1.0 - k / x) ** max(rows, 1.0))


def moe_forward_bytes(config: dict, rows: float) -> float:
    """What the grouped products of ONE forward over `rows` token rows
    must move, all expert layers: both projections of every expert some
    row chose, as served and at the published width, and the routed rows
    in and out of them (a row into up, its result out, that into down, its
    result out; activations 2 bytes)."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    wb = config["deployment"]["weight_bytes"]
    weights = experts_hit(config, rows) * 2 * h * f * wb["layers"]
    moved = rows * config["num_experts_per_tok"] * (2 * h + 2 * f) * 2
    return count(config, "moe") * (weights + moved)


def ssm_forward_bytes(config: dict, rows: float) -> float:
    """What the state updates of ONE decode forward over `rows` lanes must
    move, all Mamba layers: each lane's SSM state in and out, and the
    step's x, dt, B, C in and y out (float32). The convolution's tail is
    the convolution's, not this operation's."""
    wb = config["deployment"]["weight_bytes"]
    ssm, _ = state_bytes(config, wb["state"])
    inner, conv, _, heads = _mamba(config)
    step = (conv + heads + inner) * 4
    return count(config, "mamba") * rows * (2 * ssm + step)


def decode_step_bytes(config: dict, kv_tokens: float, lanes: float) -> float:
    """One step, a token a lane: every Mamba, attention and shared-expert
    weight and every router once, the routed experts the lanes' draws
    reach, the output head, the keys and values of `kv_tokens` of context
    in the attention layers, and `lanes` x the state and the convolution's
    tail, read and written."""
    per = layer_params(config)
    wb = config["deployment"]["weight_bytes"]
    lanes = max(lanes, 1.0)
    h = config["hidden_size"]
    dense = (count(config, "mamba") * per["mamba"]
             + count(config, "attn") * per["attn"]
             + count(config, "moe") * per["shared"]) * wb["layers"]
    routers = count(config, "moe") * config["n_routed_experts"] * h * 2
    experts = count(config, "moe") * experts_hit(config, lanes) \
        * per["expert"] * wb["layers"]
    head = h * config["vocab_size"] * wb["lm_head"]
    kv = kv_tokens * 2 * count(config, "attn") \
        * config["num_key_value_heads"] * config["head_dim"] * wb["kv"]
    state = count(config, "mamba") * lanes * 2 \
        * sum(state_bytes(config, wb["state"]))
    return dense + routers + experts + head + kv + state
