"""The expert layer's variants as facts of the configuration
(models/mixtral.py `moe_mlp`): sigmoid scores with a bias that moves the
choice and never the weights, renormalised and scaled; un-gated relu2
experts; a shared expert counted once; an expert width that is not a
multiple of 128 through the grouped product's kernel (padded with zeros,
exact); and the Mixtral / Qwen3-MoE layer as it was. float32 on the CPU:
sums of different order differ by rounding, 2e-5."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import moe_gmm
from dynamo_tpu.engine.quant import QTensor, quantize
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.mixtral import (MoeConfig, init_moe_params, moe_mlp,
                                       moe_mlp_reference, moe_route)

TOL = 2e-5


@pytest.fixture(scope="module")
def layer():
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32, pattern="E",
                                  num_layers=1)
    params = nh.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, nh._layer_params(params, "moe", 0)


def hidden(cfg, rows=12, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(rows,
                                                         cfg.hidden_size),
                       jnp.float32)


def by_hand(h, lp, cfg, shared=True):
    """The layer a token at a time in numpy, from its definition."""
    h = np.asarray(h, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in lp.items()
         if k != "expert_stacks"}
    out = np.zeros_like(h)
    relu2 = lambda v: np.square(np.maximum(v, 0.0))          # noqa: E731
    for t in range(h.shape[0]):
        s = 1.0 / (1.0 + np.exp(-(h[t] @ w["router"])))
        chosen = np.argsort(-(s + w["router_bias"]))[:cfg.experts_per_token]
        gates = cfg.routed_scaling * s[chosen] / (s[chosen].sum() + 1e-20)
        for g, e in zip(gates, chosen):
            out[t] += g * (relu2(h[t] @ w["w_up"][e]) @ w["w_down"][e])
        if shared:
            out[t] += relu2(h[t] @ w["w_shared_up"]) @ w["w_shared_down"]
    return out


def test_the_layer_equals_its_definition(layer):
    cfg, lp = layer
    h = hidden(cfg)
    np.testing.assert_allclose(moe_mlp(h, lp, cfg), by_hand(h, lp, cfg),
                               atol=TOL, rtol=TOL)


def test_the_bias_changes_the_choice_and_never_the_weight(layer):
    cfg, lp = layer
    h = hidden(cfg, rows=64)
    gates, chosen = moe_route(h, lp, cfg)
    plain = dict(lp, router_bias=jnp.zeros_like(lp["router_bias"]))
    gates0, chosen0 = moe_route(h, plain, cfg)
    assert bool(jnp.any(jnp.sort(chosen) != jnp.sort(chosen0)))
    # a weight is the UNBIASED score over the chosen's sum, times 2.5
    scores = jax.nn.sigmoid(h @ lp["router"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)
    # and a huge bias on one expert puts it into every token's choice
    # without making its weight any larger than its score allows
    forced = dict(lp, router_bias=lp["router_bias"].at[5].set(100.0))
    gates5, chosen5 = moe_route(h, forced, cfg)
    assert bool(jnp.all(jnp.any(chosen5 == 5, axis=-1)))
    assert float(gates5.max()) <= 2.5


def test_weights_are_renormalised_and_scaled(layer):
    cfg, lp = layer
    gates, _ = moe_route(hidden(cfg), lp, cfg)
    np.testing.assert_allclose(gates.sum(-1), 2.5, atol=1e-5)
    unscaled, _ = moe_route(hidden(cfg), lp,
                            dataclasses.replace(cfg, routed_scaling=1.0))
    np.testing.assert_allclose(unscaled.sum(-1), 1.0, atol=1e-5)


def test_experts_are_not_gated(layer):
    """down(relu(up(x))^2): no w_gate leaf exists, two grouped products a
    layer, and a negative pre-activation gives nothing."""
    cfg, lp = layer
    assert "w_gate" not in lp
    text = str(jax.make_jaxpr(lambda a: moe_mlp(a, lp, cfg))(hidden(cfg)))
    assert text.count("ragged_dot_general[") == 2
    flipped = dict(lp, w_up=-jnp.abs(lp["w_up"]),
                   w_shared_up=-jnp.abs(lp["w_shared_up"]))
    h = jnp.abs(hidden(cfg))
    np.testing.assert_array_equal(moe_mlp(h, flipped, cfg), 0.0)


def test_the_shared_expert_is_counted_once(layer):
    cfg, lp = layer
    h = hidden(cfg)
    routed_only = moe_mlp(h, lp, dataclasses.replace(
        cfg, shared_expert_size=0))
    np.testing.assert_allclose(routed_only, by_hand(h, lp, cfg, shared=False),
                               atol=TOL, rtol=TOL)
    shared = jnp.square(jax.nn.relu(h @ lp["w_shared_up"])) \
        @ lp["w_shared_down"]
    np.testing.assert_allclose(moe_mlp(h, lp, cfg) - routed_only, shared,
                               atol=TOL, rtol=TOL)


def test_padding_the_expert_width_is_exact(layer):
    cfg, lp = layer
    params = nh.init_params(jax.random.PRNGKey(7), cfg)
    padded = nh.pad_expert_width(params["layers"])
    assert padded["moe"]["w_up"].shape[-1] == 128       # 48 -> 128
    assert padded["moe"]["w_down"].shape[-2] == 128
    wide = nh._layer_params({"layers": padded}, "moe", 0)
    h = hidden(cfg)
    # the zeros add nothing; a longer contraction is summed in another
    # order, which float32 sees
    np.testing.assert_allclose(moe_mlp(h, wide, cfg), moe_mlp(h, lp, cfg),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_an_unaligned_width_goes_through_the_kernel_without_a_wide_copy(
        int8, monkeypatch):
    """Width 232 = 1.8125 x 128, as 1856 = 14.5 x 128: the kernel takes
    whole (128, 128) tiles, so the stack is padded to 256 once, at load;
    the kernel (interpret mode) then reads int8 and gives what the
    unpadded stack gives."""
    rs = np.random.RandomState(3)
    x_n, k, f, tile = 4, 128, 232, 8
    w_up = jnp.asarray(rs.randn(1, x_n, k, f) / 11.0, jnp.float32)
    w_down = jnp.asarray(rs.randn(1, x_n, f, k) / 15.0, jnp.float32)
    assert not moe_gmm.kernel_runs(k, f) or f % 128 == 0
    layers = {"moe": {"w_up": w_up, "w_down": w_down}}
    if int8:
        layers = {"moe": {n: quantize(w) for n, w in layers["moe"].items()}}
    padded = nh.pad_expert_width(layers)["moe"]
    ids = jnp.asarray(rs.randint(0, x_n, 24), jnp.int32)
    pos, tile_expert, n_used, sizes = moe_gmm.route_layout(ids, x_n, tile)
    rows = jnp.zeros((moe_gmm.padded_rows(24, x_n, tile), k), jnp.float32)
    rows = rows.at[pos].set(jnp.asarray(rs.randn(24, k), jnp.float32))
    used_layer = jnp.concatenate([n_used, jnp.zeros(1, jnp.int32)])

    def kernel(x, w):
        q, s = (w.q, w.s) if isinstance(w, QTensor) else (w, None)
        assert q.shape[-1] % 128 == 0 and q.shape[-2] % 128 == 0
        return moe_gmm.moe_gmm(x, q, s, tile_expert, used_layer, tile=tile,
                               interpret=True)

    def plain(x, w):
        w = w.q.astype(jnp.float32) * w.s if isinstance(w, QTensor) else w
        return jnp.einsum("mk,mkn->mn", x,
                          w[0][jnp.repeat(tile_expert, tile)])

    up = kernel(rows, padded["w_up"])
    assert up.shape[-1] == 256
    got = kernel(jnp.square(jax.nn.relu(up)), padded["w_down"])[pos]
    want = plain(jnp.square(jax.nn.relu(plain(rows, layers["moe"]["w_up"]))),
                 layers["moe"]["w_down"])[pos]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(up[pos][:, f:], 0.0)  # the padding


@pytest.mark.parametrize("qk_norm", [False, True], ids=["mixtral", "qwen3"])
def test_the_softmax_swiglu_layer_is_what_it_was(qk_norm):
    cfg = MoeConfig.tiny(dtype=jnp.float32, qk_norm=qk_norm)
    assert (cfg.router_scoring, cfg.expert_act, cfg.shared_expert_size,
            cfg.routed_scaling) == ("softmax", "swiglu", 0, 1.0)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    h = hidden(cfg)
    np.testing.assert_allclose(moe_mlp(h, lp, cfg),
                               moe_mlp_reference(h, lp, cfg), atol=TOL,
                               rtol=TOL)
    # three grouped products (gate, up, down) and no shared expert's
    text = str(jax.make_jaxpr(lambda a: moe_mlp(a, lp, cfg))(h))
    assert text.count("ragged_dot_general[") == 3
