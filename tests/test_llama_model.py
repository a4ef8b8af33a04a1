"""Model numerics: cache consistency, chunked prefill, TP sharding.

The decode-vs-full-prefill check is the strongest signal that paged cache
plumbing (scatter, gather, rope positions, masks) is correct.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.sharding import (
    cache_spec,
    make_mesh,
    param_specs,
    shard_cache,
    shard_params,
)
from dynamo_tpu.models.llama import (
    LlamaConfig,
    decode_step,
    init_cache,
    init_params,
    prefill_step,
)

set_attention_impl("xla")

CFG = LlamaConfig.tiny()


def setup_seq(cfg=CFG, tokens=tuple(range(1, 11)), num_pages=32):
    params = init_params(jax.random.PRNGKey(0), cfg)
    kc, vc = init_cache(cfg, num_pages)
    pt = np.zeros(cfg.max_pages_per_seq, dtype=np.int32)
    n_pages = (len(tokens) + 1 + cfg.page_size - 1) // cfg.page_size
    pt[:n_pages + 1] = np.arange(1, n_pages + 2)
    return params, kc, vc, jnp.asarray(pt)


def full_prefill_logits(params, cfg, tokens, pt, num_pages=32):
    kc, vc = init_cache(cfg, num_pages)
    bucket = 16
    padded = np.zeros(bucket, dtype=np.int32)
    padded[:len(tokens)] = tokens
    logits, kc, vc = prefill_step(
        params, kc, vc, jnp.asarray(padded), pt,
        jnp.int32(0), jnp.int32(len(tokens)), cfg)
    return logits, kc, vc


def test_decode_matches_full_prefill():
    tokens = list(range(1, 11))
    params, kc, vc, pt = setup_seq()
    logits, kc, vc = full_prefill_logits(params, CFG, tokens, pt)

    B = 4
    toks = np.zeros(B, dtype=np.int32)
    toks[0] = 42
    pos = np.zeros(B, dtype=np.int32)
    pos[0] = 10
    pts = np.zeros((B, CFG.max_pages_per_seq), dtype=np.int32)
    pts[0] = np.asarray(pt)
    valid = np.zeros(B, dtype=bool)
    valid[0] = True
    dl, kc, vc = decode_step(params, kc, vc, jnp.asarray(toks),
                             jnp.asarray(pos), jnp.asarray(pts),
                             jnp.asarray(valid), CFG)

    l2, _, _ = full_prefill_logits(params, CFG, tokens + [42], pt)
    assert float(jnp.max(jnp.abs(l2 - dl[0]))) < 4e-2  # bf16 tolerance


def test_chunked_prefill_matches_full():
    tokens = list(range(1, 12))
    params, kc, vc, pt = setup_seq()
    full, _, _ = full_prefill_logits(params, CFG, tokens, pt)

    kc2, vc2 = init_cache(CFG, 32)
    pad8 = np.zeros(8, dtype=np.int32)
    pad8[:8] = tokens[:8]
    _, kc2, vc2 = prefill_step(params, kc2, vc2, jnp.asarray(pad8), pt,
                               jnp.int32(0), jnp.int32(8), CFG)
    pad4 = np.zeros(4, dtype=np.int32)
    pad4[:3] = tokens[8:]
    l2, kc2, vc2 = prefill_step(params, kc2, vc2, jnp.asarray(pad4), pt,
                                jnp.int32(8), jnp.int32(11), CFG)
    assert float(jnp.max(jnp.abs(l2 - full))) < 4e-2  # bf16 tolerance


def test_padding_lanes_do_not_corrupt_cache():
    tokens = list(range(1, 9))
    params, kc, vc, pt = setup_seq()
    logits, kc, vc = full_prefill_logits(params, CFG, tokens, pt)
    kc_before = np.asarray(kc)

    # decode with 3 padding lanes; scratch page 0 absorbs their writes
    B = 4
    toks = np.full(B, 7, dtype=np.int32)
    pos = np.full(B, 60, dtype=np.int32)
    pos[0] = 8
    pts = np.zeros((B, CFG.max_pages_per_seq), dtype=np.int32)
    pts[0] = np.asarray(pt)
    valid = np.zeros(B, dtype=bool)
    valid[0] = True
    _, kc, vc = decode_step(params, kc, vc, jnp.asarray(toks),
                            jnp.asarray(pos), jnp.asarray(pts),
                            jnp.asarray(valid), CFG)
    kc_after = np.asarray(kc)
    # all real pages except the one written (page 3, slot 0) unchanged
    changed = np.argwhere(kc_before != kc_after)
    pages_touched = set(changed[:, 2].tolist())
    assert pages_touched <= {0, 3}  # scratch + the real target page


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_sharded_decode_matches_single(tp, cpu_mesh_devices):
    # kv-head axis is sharded over tp, so KVH must divide evenly
    cfg = CFG if tp == 2 else LlamaConfig.tiny(num_kv_heads=4)
    mesh = make_mesh(dp=1, tp=tp, devices=cpu_mesh_devices)
    tokens = list(range(1, 11))
    params, kc, vc, pt = setup_seq(cfg)
    ref_logits, ref_kc, ref_vc = full_prefill_logits(
        params, cfg, tokens, pt)

    sp = shard_params(params, mesh)
    skc, svc = shard_cache((init_cache(cfg, 32)), mesh)
    bucket = 16
    padded = np.zeros(bucket, dtype=np.int32)
    padded[:len(tokens)] = tokens
    logits, skc, svc = prefill_step(
        sp, skc, svc, jnp.asarray(padded), pt,
        jnp.int32(0), jnp.int32(len(tokens)), cfg)
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < 4e-2  # bf16 tolerance

    B = 2
    toks = np.array([42, 0], dtype=np.int32)
    pos = np.array([10, 0], dtype=np.int32)
    pts = np.zeros((B, cfg.max_pages_per_seq), dtype=np.int32)
    pts[0] = np.asarray(pt)
    valid = np.array([True, False])
    dl, skc, svc = decode_step(sp, skc, svc, jnp.asarray(toks),
                               jnp.asarray(pos), jnp.asarray(pts),
                               jnp.asarray(valid), cfg)
    dl_ref, _, _ = decode_step(params, ref_kc, ref_vc, jnp.asarray(toks),
                               jnp.asarray(pos), jnp.asarray(pts),
                               jnp.asarray(valid), cfg)
    assert float(jnp.max(jnp.abs(dl[0] - dl_ref[0]))) < 5e-2


def test_param_specs_cover_params():
    params = init_params(jax.random.PRNGKey(0), CFG)
    specs = param_specs()
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(flat_p) == len(flat_s)
    # every spec's sharded axes must divide the corresponding dim by tp=2,4
    def check(p, s):
        for dim, axis in zip(p.shape, s):
            if axis == "tp":
                assert dim % 4 == 0, (p.shape, s)
    jax.tree.map(
        check, params, specs,
        is_leaf=lambda x: not isinstance(x, dict))
    assert len(cache_spec()) == 4  # per-layer (KVH, N, P, D)


# ---------------------------------------------------------------------------
# One block, one geometry (ROADMAP D5): every forward flavour is
# `llama.block_qkv`, its own attention core, `llama.block_out`, and every
# consumer of the KV page's shape asks `engine/pages.py`.
# ---------------------------------------------------------------------------


def _family_cfg(family):
    """Tiny f32 configurations, one per kind of block the tree can run.
    `rms_eps` is this test's own, so every jitted entry traces afresh
    here (the call counter below counts traces, and a trace cached from
    another test of this file would read 0)."""
    from dynamo_tpu.models.mixtral import MoeConfig

    kw = dict(dtype=jnp.float32, rms_eps=1.25e-5)
    if family == "moe":
        return MoeConfig.tiny(**kw)
    return LlamaConfig.tiny(attention_bias=(family == "qkv_bias"), **kw)


@pytest.mark.parametrize("family", ["dense", "qkv_bias", "moe"])
def test_every_flavour_goes_through_the_block(family, cpu_mesh_devices,
                                              monkeypatch):
    """`prefill_batch` + `decode_step` are the reference; the mixed and
    ragged steps, the pp and sp entries, `embed_batch` and (MoE)
    `moe_forward` must give the same answers on the same tokens, AND
    each must reach them through the block's two halves,
    `llama.block_qkv` / `block_out`: a flavour that grows a layer body
    of its own again fails the counter even where its numbers still
    agree."""
    from jax.sharding import Mesh

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.llama_pp import (
        pp_decode_multi_step,
        pp_prefill_paged,
        pp_prefill_logits,
    )
    from dynamo_tpu.models.llama_sp import sp_prefill

    calls = {"qkv": 0, "out": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    from dynamo_tpu.models import llama_pp, llama_sp, mixtral

    halves = {"block_qkv": counted("qkv", llama.block_qkv),
              "block_out": counted("out", llama.block_out)}
    for mod in (llama, llama_pp, llama_sp, mixtral):   # each binds its own
        for name, fn in halves.items():
            if hasattr(mod, name):
                monkeypatch.setattr(mod, name, fn)

    def through_block(fn, *a, **kw):
        before = dict(calls)
        out = fn(*a, **kw)
        assert calls["qkv"] > before["qkv"] and calls["out"] > before["out"], (
            f"{getattr(fn, '__name__', fn)} did not go through the block")
        return out

    cfg = _family_cfg(family)
    L, P, V = cfg.num_layers, cfg.page_size, cfg.vocab_size
    tol = dict(rtol=3e-4, atol=3e-4)      # f32: test_ring_attention / moe_pp
    params = init_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(7)
    T, N = 16, 12                          # 4 pages a sequence, 12 in all
    prompts = rng.integers(1, V, (2, T)).astype(np.int32)
    nxt = rng.integers(1, V, 2).astype(np.int32)
    tables = np.zeros((2, cfg.max_pages_per_seq), np.int32)
    tables[0, :5] = 1 + np.arange(5)       # 4 prompt pages + the decode's
    tables[1, :5] = 6 + np.arange(5)
    zeros, full = np.zeros(2, np.int32), np.full(2, T, np.int32)
    J = jnp.asarray

    # -- the reference: batched paged prefill, then one decode step
    kc, vc = init_cache(cfg, N)
    ref_logits, kc, vc = through_block(
        llama.prefill_batch, params, kc, vc, J(prompts), J(tables),
        J(zeros), J(full), cfg)
    ref_kc = [np.asarray(a) for a in kc]
    ref_dec, _, _ = through_block(
        decode_step, params, kc, vc, J(nxt), J(full), J(tables),
        J(np.ones(2, bool)), cfg)
    ref_logits, ref_dec = np.asarray(ref_logits), np.asarray(ref_dec)
    ref_tok = ref_dec.argmax(-1)
    ref_lp = np.asarray(jax.nn.log_softmax(ref_dec))[np.arange(2), ref_tok]

    greedy = (J(np.zeros(2, np.uint32)), J(np.zeros(2, np.int32)),
              J(np.zeros(2, np.float32)), J(np.ones(2, np.float32)),
              J(np.zeros(2, np.int32)))

    def seq0_prefilled():
        kc, vc = init_cache(cfg, N)
        _, kc, vc = llama.prefill_batch(
            params, kc, vc, J(prompts[:1]), J(tables[:1]), J(zeros[:1]),
            J(full[:1]), cfg)
        return kc, vc

    lane0 = np.array([True, False])        # lane 1 is padding

    # -- mixed: sequence 1's prompt rides sequence 0's decode step
    kc, vc = seq0_prefilled()
    packed, ch_logits, kc, vc = through_block(
        llama.mixed_prefill_decode, params, kc, vc, J(prompts[1:]),
        J(tables[1:]), J(zeros[:1]), J(full[:1]), J(nxt), J(full),
        J(tables * lane0[:, None]), J(lane0), *greedy, cfg, 1)
    np.testing.assert_allclose(np.asarray(ch_logits)[0], ref_logits[1], **tol)
    assert int(np.asarray(packed)[0, 0, 0]) == ref_tok[0]
    np.testing.assert_allclose(np.asarray(packed)[1, 0, 0], ref_lp[0], **tol)
    for l in range(L):                     # and wrote the same KV
        np.testing.assert_allclose(np.asarray(kc[l])[:, 6:10],
                                   ref_kc[l][:, 6:10], **tol)

    # -- ragged: the same round as flat rows (16 chunk rows, 1 decode row)
    kc, vc = seq0_prefilled()
    Tb = 24
    toks, poss = np.zeros(Tb, np.int32), np.zeros(Tb, np.int32)
    pages, offs = np.zeros(Tb, np.int32), np.zeros(Tb, np.int32)
    valid, lanes = np.zeros(Tb, bool), np.zeros(Tb, np.int32)
    toks[:T], poss[:T] = prompts[1], np.arange(T)
    pages[:T], offs[:T] = tables[1][np.arange(T) // P], np.arange(T) % P
    valid[:T + 1] = True
    toks[T], poss[T], lanes[T] = nxt[0], T, 1
    pages[T], offs[T] = tables[0][T // P], T % P
    lane_tables = np.stack([tables[1], tables[0]])
    packed, ch_logits, kc, vc = through_block(
        llama.ragged_prefill_decode, params, kc, vc, J(toks), J(poss),
        J(pages), J(offs), J(valid), J(lanes), J(lane_tables),
        J(np.array([T - 1, 0], np.int32)), J(np.array([T, T + 1], np.int32)),
        *greedy, cfg)
    np.testing.assert_allclose(np.asarray(ch_logits)[0], ref_logits[1], **tol)
    assert int(np.asarray(packed)[0, 0, 0]) == ref_tok[0]
    np.testing.assert_allclose(np.asarray(packed)[1, 0, 0], ref_lp[0], **tol)

    # -- pp: paged prefill in chunks of 8 over two stages, then one decode
    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]), axis_names=("pp",))
    from dynamo_tpu.engine.pages import kv_layer_shape

    stacked = jnp.zeros((L,) + kv_layer_shape(cfg, N), cfg.dtype)
    logits, kc2, vc2 = through_block(
        pp_prefill_paged, params, stacked, stacked, J(prompts), J(tables),
        zeros, full, cfg, mesh, chunk=8)
    np.testing.assert_allclose(np.asarray(logits), ref_logits, **tol)
    packed, _, _ = through_block(
        pp_decode_multi_step, params, kc2, vc2, J(nxt), J(full), J(tables),
        J(np.ones(2, bool)), *greedy, cfg, mesh, 1, n_micro=2)
    np.testing.assert_array_equal(np.asarray(packed)[0, 0], ref_tok)
    np.testing.assert_allclose(np.asarray(packed)[1, 0], ref_lp, **tol)
    logits = through_block(pp_prefill_logits, params, J(prompts), cfg, mesh)
    np.testing.assert_allclose(np.asarray(logits), ref_logits, **tol)

    # -- sp: the ring over two devices, and the KV it exports
    sp_logits, k_all, _ = through_block(
        sp_prefill, params, J(prompts), cfg,
        Mesh(np.asarray(cpu_mesh_devices[:2]), axis_names=("sp",)))
    np.testing.assert_allclose(np.asarray(sp_logits), ref_logits, **tol)
    paged_k = ref_kc[0][:, 1:5].transpose(1, 2, 0, 3).reshape(
        T, cfg.num_kv_heads, cfg.head_dim)
    np.testing.assert_allclose(np.asarray(k_all[0, 0]), paged_k, **tol)

    # -- the cache-free forwards
    hidden, _, _ = llama.paged_forward(
        params, *init_cache(cfg, N), J(prompts), J(tables), J(zeros),
        J(full), cfg)
    lengths = np.array([T, T - 5], np.int32)
    keep = (np.arange(T)[None, :] < lengths[:, None])[..., None]
    # (causal: a position's hidden state does not see what follows it, so
    # the paged forward of the whole prompt serves both lengths)
    pooled = (np.asarray(hidden) * keep).sum(1) / lengths[:, None]
    pooled /= np.linalg.norm(pooled, axis=-1, keepdims=True)
    emb = through_block(llama.embed_batch, params, J(prompts), J(lengths),
                        cfg)
    np.testing.assert_allclose(np.asarray(emb), pooled, **tol)
    if family == "moe":
        from dynamo_tpu.models.mixtral import moe_forward

        out = through_block(moe_forward, params, J(prompts), cfg)
        np.testing.assert_allclose(np.asarray(out), ref_logits, **tol)


async def test_kv_geometry_has_one_source(cpu_mesh_devices):
    """The five former sites of the KV page's shape (init_cache, the pp
    engine's stacked cache, the KV-import check, KVBM's tier block, the
    memory ledger's page bytes) all answer what `engine/pages.py`
    answers, and that is what the device arrays really hold."""
    from jax.sharding import Mesh

    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
    from dynamo_tpu.engine.memory import kv_page_bytes as ledger_page_bytes
    from dynamo_tpu.engine.pages import (
        kv_block_shape,
        kv_layer_shape,
        kv_page_bytes,
    )
    from dynamo_tpu.kvbm.manager import KvbmConfig, KvbmManager
    from dynamo_tpu.runtime.context import Context

    # every axis a different size, so a swapped pair shows
    cfg = LlamaConfig.tiny(num_layers=2, num_kv_heads=2, page_size=4,
                           head_dim=16)
    L, KVH, P, D, N = 2, 2, 4, 16, 10
    assert kv_layer_shape(cfg, N) == (KVH, N, P, D)
    assert kv_block_shape(cfg) == (2, L, KVH, P, D)
    assert kv_block_shape(cfg, 3) == (2, L, KVH, 3, P, D)
    assert kv_page_bytes(cfg) == 2 * L * KVH * P * D * 2
    assert ledger_page_bytes(cfg, 4) == kv_page_bytes(cfg, 4)

    kc, vc = init_cache(cfg, N)
    assert len(kc) == len(vc) == L
    assert {a.shape for a in kc + vc} == {kv_layer_shape(cfg, N)}
    itemsize = kc[0].dtype.itemsize
    assert kv_page_bytes(cfg, itemsize) == sum(
        a[:, :1].nbytes for a in kc + vc)

    eng = TpuEngine(TpuEngineConfig(
        model=cfg, num_pages=N, max_batch_size=2, pp_microbatches=2,
        pp_mesh=Mesh(np.asarray(cpu_mesh_devices[:2]), axis_names=("pp",))))
    try:
        assert eng.k_cache.shape == eng.v_cache.shape \
            == (L,) + kv_layer_shape(cfg, N)
    finally:
        await eng.close()

    eng = TpuEngine(TpuEngineConfig(model=cfg, num_pages=N,
                                    max_batch_size=2))
    try:
        mgr = KvbmManager(eng, KvbmConfig(host_blocks=4))
        assert mgr.block_shape() == kv_block_shape(cfg)
        assert mgr._block_nbytes() == kv_page_bytes(cfg, itemsize)
        # what an export holds is what the import check wants...
        data = await eng.read_kv_pages([1, 2, 3])
        assert data.shape == kv_block_shape(cfg, 3)
        # ...and the check names the one source's shape when it refuses
        req = {"token_ids": list(range(1, 14)), "model": "m",
               "sampling": {"temperature": 0.0}, "stop": {"max_tokens": 2},
               "kv_transfer_params": {"kv_data": data[:, :, :, :2],
                                      "prefill_len": 12}}
        outs = [o async for o in eng.generate(req, Context())]
        assert outs[-1]["finish_reason"] == "error"
        assert str(kv_block_shape(cfg, 3)) in outs[-1]["extra"]["error"]
    finally:
        await eng.close()
