"""Routed expert dispatch (models/mixtral.py `moe_mlp`, engine/moe_gmm.py).

Rows sorted by expert and one grouped product a projection must give what
the per-token loop gives (`moe_mlp_reference`), for a few and for many
experts, float and int8 stacks, with experts no row chose, with one row;
the Pallas kernel (interpret mode) must give what the XLA path gives on the
same padded layout; and where the expert axis is sharded ('ep' in the
mesh) the dense mask is still what runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import moe_gmm
from dynamo_tpu.engine.quant import quantize
from dynamo_tpu.models import mixtral
from dynamo_tpu.models.llama import _layer_params
from dynamo_tpu.models.mixtral import (MoeConfig, init_moe_params, moe_mlp,
                                       moe_mlp_reference)


def layer(experts, k, quant, seed=0):
    cfg = MoeConfig.tiny(num_experts=experts, experts_per_token=k,
                         dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(seed), cfg)
    if quant:
        params["layers"].update({n: quantize(params["layers"][n])
                                 for n in ("w_gate", "w_up", "w_down")})
    return cfg, _layer_params(params, 0)


def dequantized(lp):
    return {n: (w.q.astype(jnp.float32) * w.s if hasattr(w, "q") else w)
            for n, w in lp.items() if n != "expert_stacks"}


# float32 weights: the routed sum adds the same k products in another
# order than the loop, 1e-5 of values of order 1. int8: both sides see the
# SAME rounded weights (the reference is handed q x s), so the tolerance
# stays the float one and does not hide the rounding.
@pytest.mark.parametrize("experts,k", [(8, 2), (128, 8)])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_routed_equals_reference(experts, k, quant):
    cfg, lp = layer(experts, k, quant)
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 7, cfg.hidden_size))
    want = moe_mlp_reference(h, dequantized(lp), cfg)
    np.testing.assert_allclose(moe_mlp(h, lp, cfg), want, atol=2e-5)


@pytest.mark.parametrize("rows", [1, 5])
def test_few_rows_leave_experts_empty(rows):
    cfg, lp = layer(128, 8, quant=True)
    h = jax.random.normal(jax.random.PRNGKey(2), (rows, cfg.hidden_size))
    # at most rows x 8 of the 128 experts are chosen: the rest are empty
    want = moe_mlp_reference(h, dequantized(lp), cfg)
    np.testing.assert_allclose(moe_mlp(h, lp, cfg), want, atol=2e-5)


def test_route_layout_places_every_row_in_its_experts_tiles():
    experts, tile = 8, 32
    ids = jnp.asarray([3, 3, 0, 7, 3, 0, 5] * 9, dtype=jnp.int32)
    pos, tile_expert, n_used, sizes = moe_gmm.route_layout(ids, experts,
                                                           tile)
    pos, tile_expert = np.asarray(pos), np.asarray(tile_expert)
    assert len(set(pos.tolist())) == len(ids)          # no two rows collide
    assert (tile_expert[pos // tile] == np.asarray(ids)).all()
    assert int(n_used[0]) * tile == int(np.asarray(sizes).sum())
    assert (np.asarray(sizes) % tile == 0).all()
    assert len(tile_expert) * tile == moe_gmm.padded_rows(len(ids), experts,
                                                          tile)


def test_row_tile_follows_the_mean_run(monkeypatch):
    # off the TPU nothing is padded: the layout is the sorted rows
    assert moe_gmm.row_tile(64 * 4 * 8, 128, 2048, 768) == 1
    monkeypatch.setattr(moe_gmm, "use_pallas", lambda: True)
    assert moe_gmm.row_tile(64 * 4 * 8, 128, 2048, 768) == 32  # block step
    assert moe_gmm.row_tile(512 * 8, 128, 2048, 768) == 32
    assert moe_gmm.row_tile(16 * 512 * 8, 128, 768, 2048) == 256  # prefill
    assert moe_gmm.row_tile(64, 4, 64, 96) == 1     # no whole (128, 128)


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_equals_xla_on_the_padded_layout(dtype, quant):
    experts, k_dim, n_dim, rows, tile = 8, 128, 256, 70, 32
    ids = jax.random.randint(jax.random.PRNGKey(0), (rows,), 0, experts)
    ids = jnp.where(ids == 3, 4, ids)                    # expert 3 is empty
    pos, tile_expert, n_used, sizes = moe_gmm.route_layout(ids, experts,
                                                           tile)
    w = jax.random.normal(jax.random.PRNGKey(1), (experts, k_dim, n_dim))
    x_rows = jax.random.normal(jax.random.PRNGKey(2),
                               (rows, k_dim)).astype(dtype)
    x = jnp.zeros((moe_gmm.padded_rows(rows, experts, tile), k_dim),
                  dtype).at[pos].set(x_rows)
    stack = quantize(w) if quant else w.astype(dtype)
    want = moe_gmm.grouped_matmul(x, stack, tile_expert, n_used, sizes,
                                  tile)[pos]
    q, s = (stack.q, stack.s) if quant else (stack, None)
    # the kernel takes the stack of all layers and the layer: here layer 1
    # of two, layer 0 poisoned
    both = jnp.stack([jnp.full_like(q, 99), q])
    scales = None if s is None else jnp.stack([s, s])
    got = moe_gmm.moe_gmm(
        x, both, scales, tile_expert,
        jnp.concatenate([n_used, jnp.asarray([1], jnp.int32)]), tile=tile,
        interpret=True)[pos]
    # the same products, accumulated in f32 on both sides, rounded to the
    # rows' dtype once: bf16 differs by its last place of values ~ 30
    atol = 1e-3 if dtype == jnp.float32 else 0.26
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    dense = jnp.einsum("rk,rkn->rn", x_rows.astype(jnp.float32),
                       (q.astype(jnp.float32) * (s if quant else 1.0))[ids])
    np.testing.assert_allclose(np.asarray(got, np.float32), dense,
                               atol=atol)


def test_int4_stacks_are_refused():
    cfg, lp = layer(8, 2, quant=False)
    lp = {**lp, "w_gate": quantize(lp["w_gate"], bits=4)}
    del lp["expert_stacks"]
    with pytest.raises(ValueError, match="W8A16 only"):
        moe_mlp(jnp.zeros((2, cfg.hidden_size)), lp, cfg)


def test_dense_path_is_kept_under_an_ep_mesh(monkeypatch):
    cfg, lp = layer(8, 2, quant=False)
    h = jax.random.normal(jax.random.PRNGKey(3), (4, cfg.hidden_size))
    taken = []
    dense = mixtral._moe_mlp_dense
    monkeypatch.setattr(mixtral, "_moe_mlp_dense",
                        lambda *a: taken.append("dense") or dense(*a))
    plain = moe_mlp(h, lp, cfg)
    assert taken == []                                   # local: routed
    mesh = jax.sharding.Mesh(np.asarray(jax.devices("cpu")[:2]), ("ep",))
    with jax.set_mesh(mesh):
        sharded = jax.jit(lambda x: moe_mlp(x, lp, cfg))(h)
    assert taken == ["dense"]
    # the two forms of the same sum: rounding only
    np.testing.assert_allclose(sharded, plain, atol=2e-5)
