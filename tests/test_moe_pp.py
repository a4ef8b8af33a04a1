"""MoE (expert-parallel) + pipeline-parallel model tests (CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import set_mesh
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.mixtral import (
    MoeConfig,
    ep_param_specs,
    init_moe_params,
    moe_forward,
    moe_mlp,
    moe_mlp_capacity,
    moe_mlp_reference,
)


def _layer0(params):
    return jax.tree.map(lambda w: w[0], params["layers"])


def test_moe_mlp_matches_per_token_reference():
    cfg = MoeConfig.tiny(dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.hidden_size),
                          jnp.float32)
    out = moe_mlp(h, _layer0(params), cfg)
    ref = moe_mlp_reference(h, _layer0(params), cfg)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_moe_topk_weights_sum_to_one():
    cfg = MoeConfig.tiny(dtype=jnp.float32, num_experts=8,
                         experts_per_token=2)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    lp = _layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 4, cfg.hidden_size),
                          jnp.float32)
    logits = (h @ lp["router"]).astype(jnp.float32)
    topv, _ = jax.lax.top_k(logits, 2)
    gates = jax.nn.softmax(topv, axis=-1)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-6)


def test_moe_forward_ep_sharded_matches_unsharded(cpu_mesh_devices):
    """Expert axis sharded over an 8-way "ep" mesh ≡ single-device —
    GSPMD computes each chip's experts locally and psums the combine."""
    cfg = MoeConfig.tiny(dtype=jnp.float32, num_experts=8)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, 255)
    ref = moe_forward(params, tokens, cfg)

    mesh = Mesh(np.asarray(cpu_mesh_devices[:8]), axis_names=("ep",))
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, ep_param_specs(),
        is_leaf=lambda x: not isinstance(x, dict))
    with set_mesh(mesh):
        out = moe_forward(sharded, tokens, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # expert weights really are distributed: each chip holds 1 of 8 experts
    shapes = {s.data.shape[1] for s in
              sharded["layers"]["w_gate"].addressable_shards}
    assert shapes == {1}


def test_capacity_dispatch_matches_dense_when_uncapped():
    """With capacity >= every expert's demand nothing drops, so the
    capacity (all-to-all) dispatch must equal the dense-dispatch math."""
    cfg = MoeConfig.tiny(dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.hidden_size),
                          jnp.float32)
    dense = moe_mlp(h, _layer0(params), cfg)
    cap = moe_mlp_capacity(h, _layer0(params), cfg,
                           capacity_factor=float(cfg.num_experts))
    np.testing.assert_allclose(np.asarray(cap), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_capacity_dispatch_drops_overflow_tokens():
    """A tiny capacity factor forces drops: dropped tokens contribute
    ZERO from the expert MLP (residual passes through), earlier tokens
    keep their slots."""
    cfg = MoeConfig.tiny(dtype=jnp.float32, num_experts=2,
                         experts_per_token=1)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.hidden_size),
                          jnp.float32)
    lp = _layer0(params)
    full = moe_mlp_capacity(h, lp, cfg, capacity_factor=8.0)
    tight = moe_mlp_capacity(h, lp, cfg, capacity_factor=0.25)  # C=1
    # with C=1 per expert at most 2 tokens total survive
    surviving = (np.abs(np.asarray(tight)).sum(-1) > 1e-6).sum()
    assert surviving <= 2
    # survivors compute exactly the uncapped value
    mask = np.abs(np.asarray(tight)).sum(-1) > 1e-6
    np.testing.assert_allclose(np.asarray(tight)[mask],
                               np.asarray(full)[mask], rtol=2e-4,
                               atol=2e-4)


def test_capacity_forward_ep_sharded_matches_unsharded(cpu_mesh_devices):
    """moe_forward(dispatch="capacity") under an 8-way ep mesh == single
    device: GSPMD lowers the dispatch einsum to the expert all-to-all."""
    cfg = MoeConfig.tiny(dtype=jnp.float32, num_experts=8)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, 255)
    ref = moe_forward(params, tokens, cfg, dispatch="capacity")

    mesh = Mesh(np.asarray(cpu_mesh_devices[:8]), axis_names=("ep",))
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, ep_param_specs(),
        is_leaf=lambda x: not isinstance(x, dict))
    with set_mesh(mesh):
        out = moe_forward(sharded, tokens, cfg, dispatch="capacity")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# pipeline parallel

def test_pp_prefill_matches_dense(cpu_mesh_devices):
    from dynamo_tpu.models.llama import LlamaConfig, init_params
    from dynamo_tpu.models.llama_pp import pp_prefill_logits
    from dynamo_tpu.models.llama_sp import sp_prefill

    cfg = LlamaConfig.tiny(dtype=jnp.float32, num_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, T = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 1, 255)

    # reference: sp_prefill on a 1-device mesh == plain dense forward
    ref_mesh = Mesh(np.asarray(cpu_mesh_devices[:1]), axis_names=("sp",))
    ref_logits, _, _ = sp_prefill(params, tokens, cfg, ref_mesh)

    for stages, micro in ((2, 2), (4, 4), (4, 1)):
        mesh = Mesh(np.asarray(cpu_mesh_devices[:stages]),
                    axis_names=("pp",))
        out = pp_prefill_logits(params, tokens, cfg, mesh, n_micro=micro)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_logits), rtol=3e-4, atol=3e-4,
            err_msg=f"pp={stages} M={micro}")


def test_pp_rejects_bad_geometry(cpu_mesh_devices):
    from dynamo_tpu.models.llama import LlamaConfig, init_params
    from dynamo_tpu.models.llama_pp import pp_prefill_logits

    cfg = LlamaConfig.tiny(num_layers=3)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]), axis_names=("pp",))
    with pytest.raises(AssertionError):
        pp_prefill_logits(params,
                          jnp.ones((2, 8), jnp.int32), cfg, mesh)


def test_pp_weights_are_stage_sharded(cpu_mesh_devices):
    from dynamo_tpu.models.llama import LlamaConfig, init_params
    from dynamo_tpu.models.llama_pp import pp_param_specs

    cfg = LlamaConfig.tiny(num_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = Mesh(np.asarray(cpu_mesh_devices[:4]), axis_names=("pp",))
    wq = jax.device_put(
        params["layers"]["wq"],
        NamedSharding(mesh, pp_param_specs()["layers"]["wq"]))
    # each stage holds exactly 1 of the 4 layers' weights
    assert {s.data.shape[0] for s in wq.addressable_shards} == {1}


def test_pp_decode_matches_single_device_decode(cpu_mesh_devices):
    """pp=2 microbatched decode emits tokens identical to the plain
    fused decode loop on the same weights (greedy)."""
    from dynamo_tpu.engine.attention import set_attention_impl
    from dynamo_tpu.models.llama import (
        LlamaConfig,
        decode_multi_step,
        init_cache,
        init_params,
    )
    from dynamo_tpu.models.llama_pp import pp_decode_multi_step

    set_attention_impl("xla")
    cfg = LlamaConfig.tiny(num_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, K = 4, 6
    n_pages = 1 + B * 4
    tokens = np.asarray([7, 11, 13, 17], dtype=np.int32)
    positions = np.zeros(B, dtype=np.int32)
    tables = np.zeros((B, cfg.max_pages_per_seq), dtype=np.int32)
    for i in range(B):
        tables[i, :4] = 1 + 4 * i + np.arange(4)
    valid = np.ones(B, dtype=bool)
    z = np.zeros(B, dtype=np.uint32)
    temps = np.zeros(B, dtype=np.float32)
    tps = np.ones(B, dtype=np.float32)
    tks = np.zeros(B, dtype=np.int32)

    kc, vc = init_cache(cfg, n_pages)
    ref, _, _ = decode_multi_step(
        params, kc, vc, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(tables), jnp.asarray(valid), jnp.asarray(z),
        jnp.asarray(z), jnp.asarray(temps), jnp.asarray(tps),
        jnp.asarray(tks), cfg, K)
    ref = np.asarray(ref)

    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]), axis_names=("pp",))
    shape = (cfg.num_layers, cfg.num_kv_heads, n_pages, cfg.page_size,
             cfg.head_dim)
    kc2 = jnp.zeros(shape, cfg.dtype)
    vc2 = jnp.zeros(shape, cfg.dtype)
    packed, kc2, vc2 = pp_decode_multi_step(
        params, kc2, vc2, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(tables), jnp.asarray(valid), jnp.asarray(z),
        jnp.asarray(z), jnp.asarray(temps), jnp.asarray(tps),
        jnp.asarray(tks), cfg, mesh, K, n_micro=2)
    got = np.asarray(packed)
    np.testing.assert_array_equal(got[0], ref[0])
    # logprobs see bf16 re-association across the stage split: tokens
    # are bit-identical, the float diagnostics are merely close
    np.testing.assert_allclose(got[1], ref[1], atol=5e-2)


def test_pp_decode_stochastic_seeded_matches(cpu_mesh_devices):
    """Seeded sampling through the pipeline consumes the same (seed,
    step) stream as the plain loop. bf16 re-association across the
    stage split can flip genuine near-ties (random tiny-model logits
    are nearly flat), so assert strong agreement; the greedy test above
    is the bit-exact one."""
    from dynamo_tpu.engine.attention import set_attention_impl
    from dynamo_tpu.models.llama import (
        LlamaConfig,
        decode_multi_step,
        init_cache,
        init_params,
    )
    from dynamo_tpu.models.llama_pp import pp_decode_multi_step

    set_attention_impl("xla")
    cfg = LlamaConfig.tiny(num_layers=2)
    params = init_params(jax.random.PRNGKey(1), cfg)
    B, K = 4, 5
    n_pages = 1 + B * 4
    tokens = np.asarray([3, 5, 7, 9], dtype=np.int32)
    positions = np.zeros(B, dtype=np.int32)
    tables = np.zeros((B, cfg.max_pages_per_seq), dtype=np.int32)
    for i in range(B):
        tables[i, :4] = 1 + 4 * i + np.arange(4)
    valid = np.ones(B, dtype=bool)
    seeds = np.arange(B, dtype=np.uint32) + 5
    z = np.zeros(B, dtype=np.uint32)
    temps = np.full(B, 0.9, dtype=np.float32)
    tps = np.full(B, 0.9, dtype=np.float32)
    tks = np.zeros(B, dtype=np.int32)

    kc, vc = init_cache(cfg, n_pages)
    ref, _, _ = decode_multi_step(
        params, kc, vc, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(tables), jnp.asarray(valid), jnp.asarray(seeds),
        jnp.asarray(z), jnp.asarray(temps), jnp.asarray(tps),
        jnp.asarray(tks), cfg, K)
    ref = np.asarray(ref)

    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]), axis_names=("pp",))
    shape = (cfg.num_layers, cfg.num_kv_heads, n_pages, cfg.page_size,
             cfg.head_dim)
    packed, _, _ = pp_decode_multi_step(
        params, jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype),
        jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(tables),
        jnp.asarray(valid), jnp.asarray(seeds), jnp.asarray(z),
        jnp.asarray(temps), jnp.asarray(tps), jnp.asarray(tks),
        cfg, mesh, K, n_micro=4)
    got = np.asarray(packed)[0]
    agree = (got == ref[0]).mean()
    assert agree >= 0.8, (agree, got, ref[0])


def test_pp_prefill_paged_matches_prefill_batch(cpu_mesh_devices):
    """Chunk-microbatched pp prefill writes the same paged KV and
    produces the same last-token logits as the sequential
    prefill_batch — the serving-path prerequisite for pp engines."""
    from dynamo_tpu.engine.attention import set_attention_impl
    from dynamo_tpu.models.llama import (
        LlamaConfig,
        init_cache,
        init_params,
        prefill_batch,
    )
    from dynamo_tpu.models.llama_pp import pp_prefill_paged

    set_attention_impl("xla")
    cfg = LlamaConfig.tiny(num_layers=4)
    params = init_params(jax.random.PRNGKey(3), cfg)
    B, T = 2, 16                            # 4 chunks of 4
    n_pages = 1 + B * (T // cfg.page_size)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (B, T)).astype(np.int32)
    tables = np.zeros((B, cfg.max_pages_per_seq), dtype=np.int32)
    per = T // cfg.page_size
    for i in range(B):
        tables[i, :per] = 1 + per * i + np.arange(per)
    cached = np.zeros(B, dtype=np.int32)
    # lane 1 is shorter: its tail positions must be masked, logits taken
    # from its own last token's chunk
    seq_lens = np.asarray([T, T - 6], dtype=np.int32)

    kc, vc = init_cache(cfg, n_pages)
    ref_logits, kc_ref, vc_ref = prefill_batch(
        params, kc, vc, jnp.asarray(tokens), jnp.asarray(tables),
        jnp.asarray(cached), jnp.asarray(seq_lens), cfg)

    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]), axis_names=("pp",))
    shape = (cfg.num_layers, cfg.num_kv_heads, n_pages, cfg.page_size,
             cfg.head_dim)
    logits, kc2, vc2 = pp_prefill_paged(
        params, jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype),
        jnp.asarray(tokens), jnp.asarray(tables), cached, seq_lens, cfg,
        mesh, chunk=4)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               atol=5e-2, rtol=5e-2)
    # the paged KV the decode path will read must match the sequential
    # loop's writes (valid pages only; page 0 is scratch)
    for l in range(cfg.num_layers):
        np.testing.assert_allclose(
            np.asarray(kc2[l][:, 1:n_pages], np.float32),
            np.asarray(kc_ref[l][:, 1:n_pages], np.float32),
            atol=5e-2, rtol=5e-2)
        np.testing.assert_allclose(
            np.asarray(vc2[l][:, 1:n_pages], np.float32),
            np.asarray(vc_ref[l][:, 1:n_pages], np.float32),
            atol=5e-2, rtol=5e-2)
