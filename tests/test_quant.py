"""Int8 weight-only quantization (engine/quant.py).

Scheme check: per-output-channel symmetric int8 with the scale applied to
the matmul output is EXACT w.r.t. quantizing the weight itself —
``(x @ q) * s == x @ (q * s)`` — so the only error is the int8 rounding
of W, bounded by s/2 per element.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.quant import (
    QTensor,
    qm,
    quantize,
    quantize_params,
)
from dynamo_tpu.models.llama import (
    LlamaConfig,
    init_cache,
    init_params,
    prefill_step,
)

set_attention_impl("xla")

CFG = LlamaConfig.tiny()


def test_quantize_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    qt = quantize(w)
    assert qt.q.dtype == jnp.int8 and qt.q.shape == w.shape
    assert qt.s.shape == (1, 32)
    deq = qt.q.astype(jnp.float32) * qt.s
    # rounding error ≤ s/2 per element
    assert np.all(np.abs(np.asarray(deq - w)) <= np.asarray(qt.s) / 2 + 1e-7)


def test_qm_matches_dequantized_matmul():
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(k[0], (4, 64), jnp.float32)
    w = jax.random.normal(k[1], (64, 32), jnp.float32)
    qt = quantize(w)
    got = qm(x, qt)
    want = x @ (qt.q.astype(jnp.float32) * qt.s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and close to the unquantized product (int8 rounding only)
    err = float(jnp.max(jnp.abs(got - x @ w)))
    assert err < 0.05 * float(jnp.max(jnp.abs(x @ w)))


def test_host_quantize_matches_device_and_is_idempotent():
    """quantize_params_host must produce bit-identical q/s to the device
    path (same rounding), and both paths must pass QTensor leaves
    through unchanged (pre-quantized checkpoints)."""
    import ml_dtypes

    from dynamo_tpu.engine.quant import (
        quantize_host,
        quantize_params,
        quantize_params_host,
    )

    rng = np.random.default_rng(0)
    w = rng.standard_normal((3, 64, 32), dtype=np.float32) \
        .astype(ml_dtypes.bfloat16)
    host = quantize_host(w)
    dev = quantize(jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(host.q), np.asarray(dev.q))
    np.testing.assert_allclose(np.asarray(host.s), np.asarray(dev.s),
                               rtol=1e-6)
    # idempotence through the full-pytree entrypoints
    params = {"embed": w[0], "layers": {"wq": w, "attn_norm": w[0]},
              "lm_head": w[0]}
    hq = quantize_params_host(params)
    assert not isinstance(hq["layers"]["attn_norm"], QTensor)
    again = quantize_params(hq)
    assert again["layers"]["wq"] is hq["layers"]["wq"]
    assert again["lm_head"] is hq["lm_head"]


async def test_engine_places_host_params_on_device_once():
    """Caller-provided numpy checkpoints must be device_put at init —
    a numpy leaf reaching the jitted step would re-upload the full
    weights every call."""
    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig

    cfg = LlamaConfig.tiny()
    host = jax.tree.map(np.asarray, init_params(
        jax.random.PRNGKey(0), cfg))
    eng = TpuEngine(TpuEngineConfig(model=cfg, num_pages=16,
                                    max_batch_size=2))
    eng2 = TpuEngine(TpuEngineConfig(model=cfg, num_pages=16,
                                     max_batch_size=2), params=host)
    for leaf in jax.tree.leaves(eng2.params):
        assert hasattr(leaf, "devices"), type(leaf)
    await eng.close()
    await eng2.close()


def test_qm_plain_array_passthrough():
    x = jnp.ones((2, 8), jnp.bfloat16)
    w = jnp.ones((8, 4), jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(qm(x, w)), np.asarray(x @ w))


def test_quantized_params_halve_bytes():
    params = init_params(jax.random.PRNGKey(0), CFG)
    qp = quantize_params(params)
    dense = sum(x.nbytes for k, x in params["layers"].items()
                if k not in ("attn_norm", "mlp_norm"))
    qdense = sum(qp["layers"][k].nbytes for k in qp["layers"]
                 if k not in ("attn_norm", "mlp_norm"))
    assert qdense < 0.6 * dense
    assert isinstance(qp["layers"]["wq"], QTensor)
    assert isinstance(qp["lm_head"], QTensor)
    # embeddings/norms untouched
    assert qp["embed"] is params["embed"]


def test_layer_slice_maps_through_qtensor():
    # models/llama.py _layer_params tree-maps w[l] over the layer dict;
    # QTensor must slice q and s together
    params = quantize_params(init_params(jax.random.PRNGKey(0), CFG))
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    assert lp["wq"].q.ndim == 2
    assert lp["wq"].s.shape == (1, CFG.num_heads * CFG.head_dim)


def test_prefill_logits_close_to_bf16():
    tokens = list(range(1, 11))
    params = init_params(jax.random.PRNGKey(0), CFG)
    pt = np.zeros(CFG.max_pages_per_seq, dtype=np.int32)
    pt[:4] = np.arange(1, 5)
    pt = jnp.asarray(pt)

    def run(p):
        kc, vc = init_cache(CFG, 32)
        padded = np.zeros(16, dtype=np.int32)
        padded[:len(tokens)] = tokens
        logits, _, _ = prefill_step(p, kc, vc, jnp.asarray(padded), pt,
                                    jnp.int32(0), jnp.int32(len(tokens)),
                                    CFG)
        return np.asarray(logits)

    base = run(params)
    quant = run(quantize_params(params))
    scale = np.abs(base).max()
    assert np.abs(quant - base).max() < 0.1 * scale


async def test_engine_int8_generates_deterministically():
    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
    from dynamo_tpu.runtime.context import Context

    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2, quantize="int8",
        default_max_tokens=8))
    req = {"token_ids": [1, 2, 3, 4, 5], "model": "m",
           "sampling": {"temperature": 0.0}, "stop": {"max_tokens": 8}}

    async def collect():
        toks = []
        async for o in eng.generate(dict(req), Context()):
            toks += o.get("token_ids", [])
        return toks

    a = await collect()
    b = await collect()
    assert len(a) == 8 and a == b
    await eng.close()


def test_sharded_quantized_prefill_matches_unsharded(cpu_mesh_devices):
    from dynamo_tpu.engine.sharding import make_mesh, shard_cache, shard_params

    mesh = make_mesh(dp=1, tp=2, devices=cpu_mesh_devices)
    tokens = list(range(1, 11))
    params = quantize_params(init_params(jax.random.PRNGKey(0), CFG))
    pt = np.zeros(CFG.max_pages_per_seq, dtype=np.int32)
    pt[:4] = np.arange(1, 5)
    pt = jnp.asarray(pt)
    padded = np.zeros(16, dtype=np.int32)
    padded[:len(tokens)] = tokens

    kc, vc = init_cache(CFG, 32)
    ref, _, _ = prefill_step(params, kc, vc, jnp.asarray(padded), pt,
                             jnp.int32(0), jnp.int32(len(tokens)), CFG)

    sp = shard_params(params, mesh)
    assert isinstance(sp["layers"]["wq"], QTensor)
    skc, svc = shard_cache(init_cache(CFG, 32), mesh)
    got, _, _ = prefill_step(sp, skc, svc, jnp.asarray(padded), pt,
                             jnp.int32(0), jnp.int32(len(tokens)), CFG)
    assert float(jnp.max(jnp.abs(got - ref))) < 4e-2


def test_int4_quantize_roundtrip_and_qm():
    from dynamo_tpu.engine.quant import _unpack4

    w = jax.random.normal(jax.random.PRNGKey(5), (64, 32), jnp.float32)
    qt = quantize(w, bits=4)
    # physical leaf is nibble-packed int8 (no S4 dtype at any boundary)
    assert str(qt.q.dtype) == "int8" and qt.bits == 4
    assert qt.q.shape == (64, 16) and qt.shape == w.shape
    unpacked = jax.jit(_unpack4)(qt.q)
    assert unpacked.shape == w.shape
    deq = unpacked.astype(jnp.float32) * qt.s
    # rounding error <= s/2 per element at 4 bits
    assert np.all(np.abs(np.asarray(deq - w)) <= np.asarray(qt.s) / 2
                  + 1e-6)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 64), jnp.float32)
    got = qm(x, qt)
    want = x @ (np.asarray(unpacked, np.float32) * np.asarray(qt.s))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_int4_pack_unpack_exact():
    from dynamo_tpu.engine.quant import _unpack4, pack4

    q = jax.random.randint(jax.random.PRNGKey(7), (16, 32), -7, 8,
                           jnp.int8)
    assert np.array_equal(np.asarray(jax.jit(_unpack4)(pack4(q))),
                          np.asarray(q))


def test_int4_params_lm_head_stays_int8():
    from dynamo_tpu.engine.quant import quantize_params

    params = init_params(jax.random.PRNGKey(0), CFG)
    q = quantize_params(params, mode="int4")
    assert q["layers"]["w_gate"].bits == 4
    assert q["lm_head"].bits == 8                # logit quality


async def test_engine_int4_serves_and_tracks_int8():
    """int4 engine generates; greedy output strongly agrees with the
    int8 engine on the same weights (quality smoke)."""
    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
    from dynamo_tpu.runtime.context import Context

    params = init_params(jax.random.PRNGKey(2), CFG)

    async def run(mode):
        eng = TpuEngine(TpuEngineConfig(model=CFG, num_pages=32,
                                        max_batch_size=2,
                                        decode_steps_per_sync=4,
                                        quantize=mode), params=params)
        req = {"token_ids": [5, 6, 7], "model": "m",
               "sampling": {"temperature": 0.0},
               "stop": {"max_tokens": 12}}
        toks = [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", ())]
        await eng.close()
        return toks

    t8, t4 = await run("int8"), await run("int4")
    assert len(t4) == 12
    # a 64-dim random model is the worst case for 4-bit rounding: one
    # divergent step cascades. The first token (pure prefill logits)
    # must agree; sequence-level quality lives in the bench extra on
    # the big model.
    assert t4[0] == t8[0], (t8, t4)


def test_w8a8_mode_marks_act_bits_and_serves():
    """quantize_params(mode="w8a8") marks weights for the native-int8
    MXU matmul path; off-TPU qm falls back to the exact W8A16 math, so
    a w8a8 engine on CPU matches the int8 engine token for token (the
    activation quantization is a TPU-kernel-path approximation)."""
    import asyncio

    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
    from dynamo_tpu.engine.quant import quantize_params
    from dynamo_tpu.runtime.context import Context

    params = init_params(jax.random.PRNGKey(2), CFG)
    qp = quantize_params(params, mode="w8a8")
    assert qp["layers"]["w_gate"].act_bits == 8
    assert qp["layers"]["w_gate"].bits == 8
    assert qp["lm_head"].act_bits == 16        # logit quality
    # aux survives tree round-trips (jit/donation/sharding flatten it)
    leaves, treedef = jax.tree.flatten(qp)
    back = jax.tree.unflatten(treedef, leaves)
    assert back["layers"]["w_gate"].act_bits == 8

    async def run(mode):
        eng = TpuEngine(TpuEngineConfig(model=CFG, num_pages=32,
                                        max_batch_size=2,
                                        decode_steps_per_sync=4,
                                        quantize=mode), params=params)
        req = {"token_ids": [5, 6, 7], "model": "m",
               "sampling": {"temperature": 0.0},
               "stop": {"max_tokens": 10}}
        toks = [t async for o in eng.generate(req, Context())
                for t in o.get("token_ids", ())]
        await eng.close()
        return toks

    t8 = asyncio.run(run("int8"))
    t88 = asyncio.run(run("w8a8"))
    assert t88 == t8 and len(t88) == 10


def test_pallas_a8_kernels_interpret_mode(monkeypatch):
    """Hermetic correctness of the int4 (W4A8) and w8a8 pallas kernels
    via interpret mode: outputs must match the plain-XLA quantized
    reference to A8-rounding tolerance."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    from dynamo_tpu.engine.int4_mm import int4_matmul, w8a8_matmul
    from dynamo_tpu.engine.quant import _unpack4

    key = jax.random.PRNGKey(0)
    K, N = 256, 256
    w = jax.random.normal(key, (K, N), jnp.float32) / 20
    x = (jax.random.normal(jax.random.PRNGKey(1), (8, K),
                           jnp.float32) / 8).astype(jnp.float32)

    qt8 = quantize(w, bits=8)
    y88 = np.asarray(w8a8_matmul(x, qt8.q, qt8.s), np.float32)
    ref8 = np.asarray(x @ (qt8.q.astype(jnp.float32) * qt8.s),
                      np.float32)
    rel8 = np.abs(y88 - ref8).max() / np.abs(ref8).max()
    assert rel8 < 0.02, rel8          # A8 rounding only

    qt4 = quantize(w, bits=4)
    y4 = np.asarray(int4_matmul(x, qt4.q, qt4.s), np.float32)
    wq4 = np.asarray(jax.jit(_unpack4)(qt4.q), np.float32)
    ref4 = np.asarray(x, np.float32) @ (wq4 * np.asarray(qt4.s))
    rel4 = np.abs(y4 - ref4).max() / np.abs(ref4).max()
    assert rel4 < 0.02, rel4
