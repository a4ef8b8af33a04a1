"""Mesh & collective flight recorder (engine/collectives.py,
runtime/topology.py).

The load-bearing pins:

1. **HLO parity** — on a real tp=2 CPU mesh, the collective bytes the
   recorder extracts from the *compiled* HLO of a megatron-sharded
   llama layer stack equal the hand-computed analytic set
   (`megatron_collectives`): two all-reduces per layer, each moving
   2·(n−1)·tokens·hidden·dtype_bytes. If GSPMD's sharding choices ever
   drift (an extra reshard, a reduce-scatter rewrite), this fails
   chip-free.
2. **Byte-identical unarmed path** — without DYN_MESH_RECORDER the
   engine holds NO recorder object, and arming it changes neither the
   emitted tokens nor the deterministic scheduler counters.
3. **Reshard manifest** — a recompile whose collective set grows past
   the entry's first-compile manifest counts, warns, and drops a ring
   event; an equal or shrinking set does not.
4. **Topology** — link-tier classification (local/ici/dcn) and the
   pull-path mapping are pure functions of device attributes.
"""

import asyncio
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.collectives import (
    CollectiveRecorder,
    MeshMetrics,
    compiled_hlo_text,
    megatron_collectives,
    mesh_axis_groups,
    mesh_payload,
    mesh_recorder_from_env,
    parse_collectives,
    wire_bytes,
)
from dynamo_tpu.engine.sharding import make_mesh, shard_params
from dynamo_tpu.runtime.topology import (
    classify_link,
    link_bandwidths,
    link_cost,
    link_for_pull_path,
    topology_summary,
)

pytestmark = pytest.mark.tier0


# ---------------------------------------------------------------------------
# analytic formulas + HLO parser units
# ---------------------------------------------------------------------------


def test_wire_bytes_formulas():
    r, n = 1000, 4
    assert wire_bytes("all-reduce", r, n) == 2 * 3 * r
    assert wire_bytes("all-gather", r, n) == 3 * r
    assert wire_bytes("reduce-scatter", r, n) == 4 * 3 * r
    assert wire_bytes("all-to-all", r, n) == 3 * r
    assert wire_bytes("collective-permute", r, n, pairs=7) == 7 * r
    assert wire_bytes("all-reduce", r, n, num_groups=2) == 2 * 2 * 3 * r
    assert wire_bytes("unknown-op", r, n) == 0


def test_parse_explicit_groups_and_axis_attribution():
    axis_groups = {"dp": [(0, 2), (1, 3)], "tp": [(0, 1), (2, 3)]}
    hlo = (
        "  %all-reduce.1 = f32[4,64]{1,0} all-reduce(f32[4,64]{1,0} %x),"
        " replica_groups={{0,1},{2,3}}, to_apply=%add\n"
        "  %ag = f32[8,64]{1,0} all-gather(f32[4,64]{1,0} %y),"
        " replica_groups={{0,2},{1,3}}, dimensions={0}\n"
    )
    ops = parse_collectives(hlo, axis_groups, 4)
    assert [o["op"] for o in ops] == ["all-reduce", "all-gather"]
    ar, ag = ops
    assert ar["axis"] == "tp" and ar["num_groups"] == 2
    assert ar["result_bytes"] == 4 * 64 * 4
    assert ar["bytes"] == 2 * 2 * 1 * ar["result_bytes"]
    assert ag["axis"] == "dp"
    assert ag["bytes"] == 2 * 1 * 8 * 64 * 4


def test_parse_iota_groups_tuple_results_and_async_pairs():
    axis_groups = {"tp": [(0, 1), (2, 3)]}
    hlo = (
        # iota form [2,2]<=[4] → {{0,1},{2,3}}; tuple result sums both
        "  %ar = (bf16[8]{0}, bf16[24]{0}) all-reduce-start(...),"
        " replica_groups=[2,2]<=[4], to_apply=%add\n"
        # the matching -done must NOT double count
        "  %d = (bf16[8]{0}, bf16[24]{0}) all-reduce-done(%ar)\n"
    )
    ops = parse_collectives(hlo, axis_groups, 4)
    assert len(ops) == 1
    assert ops[0]["axis"] == "tp"
    assert ops[0]["result_bytes"] == (8 + 24) * 2
    assert ops[0]["bytes"] == 2 * 1 * (8 + 24) * 2 * 2


def test_parse_collective_permute_components():
    axis_groups = {"sp": [(0, 1, 2, 3)]}
    hlo = ("  %cp = f32[16]{0} collective-permute(f32[16]{0} %x),"
           " source_target_pairs={{0,1},{1,2},{2,3},{3,0}}\n")
    ops = parse_collectives(hlo, axis_groups, 4)
    assert len(ops) == 1
    assert ops[0]["op"] == "collective-permute"
    assert ops[0]["axis"] == "sp"       # ring decomposes to sp's group
    assert ops[0]["bytes"] == 4 * 16 * 4


def test_mesh_axis_groups_flattened_positions(cpu_mesh_devices):
    mesh = make_mesh(dp=2, tp=4, devices=cpu_mesh_devices)
    groups = mesh_axis_groups(mesh)
    assert groups["tp"] == [(0, 1, 2, 3), (4, 5, 6, 7)]
    assert groups["dp"] == [(0, 4), (1, 5), (2, 6), (3, 7)]


def test_megatron_collectives_formula():
    rows = megatron_collectives(layers=3, tokens=16, hidden=64, tp=2,
                                dtype_bytes=4)
    assert len(rows) == 1
    r = rows[0]
    assert r["op"] == "all-reduce" and r["axis"] == "tp"
    assert r["count"] == 6
    assert r["bytes"] == 6 * 2 * 1 * (16 * 64 * 4)
    assert megatron_collectives(layers=3, tokens=16, hidden=64, tp=1) \
        == []


# ---------------------------------------------------------------------------
# the tp=2 HLO-vs-analytic parity pin
# ---------------------------------------------------------------------------


def test_tp2_llama_layers_hlo_matches_megatron_formula(cpu_mesh_devices):
    """Compile the real dense llama layer stack megatron-sharded over
    tp=2 and check the recorder's HLO-extracted collective bytes equal
    the hand-computed analytic set exactly."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.models.llama import (
        LlamaConfig,
        _layer_params,
        dense_layer,
        init_params,
    )

    # KVH == H so GQA head-repeat can't force its own collective; f32
    # so CPU XLA can't upcast activations behind the byte math
    cfg = LlamaConfig.tiny(num_kv_heads=4)
    mesh = make_mesh(dp=1, tp=2, devices=cpu_mesh_devices)
    params = jax.tree.map(
        lambda w: w.astype(jnp.float32) if w.dtype == jnp.bfloat16 else w,
        init_params(jax.random.PRNGKey(0), cfg))
    sp = shard_params(params, mesh)

    B, T = 2, 8
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (B, T, cfg.hidden_size)), dtype=jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P(None, None, None)))

    def layers_fwd(p, h):
        positions = jnp.arange(T)[None, :]
        mask = jnp.tril(jnp.ones((T, T), bool))
        for l in range(cfg.num_layers):
            h = dense_layer(h, _layer_params(p, l), positions, mask, cfg)
        return h

    fn = jax.jit(layers_fwd,
                 out_shardings=NamedSharding(mesh, P(None, None, None)))
    hlo = compiled_hlo_text(fn, (sp, x))
    assert hlo is not None
    ops = parse_collectives(hlo, mesh_axis_groups(mesh), 2)

    expected = megatron_collectives(
        layers=cfg.num_layers, tokens=B * T, hidden=cfg.hidden_size,
        tp=2, dtype_bytes=4)[0]
    ars = [o for o in ops if o["op"] == "all-reduce"]
    assert len(ars) == expected["count"]        # 2 per layer, no extras
    for o in ars:
        assert o["axis"] == "tp"
        assert o["result_bytes"] == expected["result_bytes"]
    assert sum(o["bytes"] for o in ops) == expected["bytes"]

    # and the recorder's compile-observation path lands the same total
    rec = CollectiveRecorder(metrics=MeshMetrics(), mesh=mesh)
    rec.observe_compile("dense_fwd", (B, T), fn, (sp, x))
    rec.record_dispatch("dense_fwd", (B, T))
    s = rec.summary()
    assert s["entries"]["dense_fwd"]["bytes_total"] == expected["bytes"]
    assert s["manifest"]["dense_fwd"] == ["all-reduce/tp"]


# ---------------------------------------------------------------------------
# unarmed path: no recorder, identical serving
# ---------------------------------------------------------------------------


def _run_engine_tokens(n_tokens: int = 12):
    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.runtime.context import Context

    eng = TpuEngine(TpuEngineConfig(
        model=LlamaConfig.tiny(), num_pages=64, max_batch_size=2,
        default_max_tokens=n_tokens))

    async def run():
        toks = []
        async for o in eng.generate(
                {"token_ids": [1, 2, 3, 4, 5], "model": "m",
                 "sampling": {"temperature": 0.0},
                 "stop": {"max_tokens": n_tokens}}, Context()):
            toks += o.get("token_ids", [])
        stats = {"prefill_chunks": eng.perf["prefill_chunks"],
                 "mixed_steps": eng.perf["mixed_steps"],
                 "compiles": eng.metrics.compile.total}
        await eng.close()
        return toks, stats, eng

    return asyncio.run(run())


def test_unarmed_engine_has_no_recorder_and_serving_is_identical(
        monkeypatch):
    monkeypatch.delenv("DYN_MESH_RECORDER", raising=False)
    base_toks, base_stats, eng = _run_engine_tokens()
    assert eng.mesh_recorder is None
    payload = mesh_payload(eng)
    assert payload["enabled"] is False and "hint" in payload

    monkeypatch.setenv("DYN_MESH_RECORDER", "1")
    armed_toks, armed_stats, armed_eng = _run_engine_tokens()
    rec = armed_eng.mesh_recorder
    assert rec is not None
    assert armed_toks == base_toks
    assert armed_stats == base_stats
    # the recorder actually observed the dispatches it rode along with
    s = rec.summary()
    assert s["dispatches"] > 0 and s["compiles"] > 0
    assert any(e["analyzed"] for e in s["entries"].values())
    armed_payload = mesh_payload(armed_eng, limit=8)
    assert armed_payload["enabled"] is True
    assert armed_payload["topology"]["n_devices"] == len(jax.devices())


def test_recorder_from_env_gating(monkeypatch):
    assert mesh_recorder_from_env(env={}) is None
    assert mesh_recorder_from_env(env={"DYN_MESH_RECORDER": "0"}) is None
    rec = mesh_recorder_from_env(
        env={"DYN_MESH_RECORDER": "1", "DYN_MESH_RECORDER_RING": "32"})
    assert rec is not None and rec.capacity == 32


# ---------------------------------------------------------------------------
# reshard manifest
# ---------------------------------------------------------------------------


def test_reshard_manifest_trips_on_growth_only():
    mm = MeshMetrics()
    rec = CollectiveRecorder(metrics=mm)
    ar = {"op": "all-reduce", "axis": "tp", "result_bytes": 64,
          "group_size": 2, "num_groups": 1, "count": 2, "bytes": 256}
    ag = {"op": "all-gather", "axis": "dp", "result_bytes": 64,
          "group_size": 2, "num_groups": 1, "count": 1, "bytes": 64}

    rec.ingest("prefill", (1, 16), [ar])          # freezes the manifest
    rec.ingest("prefill", (1, 32), [ar])          # same set: no trip
    assert rec.summary()["reshards"] == {}

    rec.ingest("prefill", (1, 64), [ar, ag])      # grew: reshard
    s = rec.summary()
    assert s["reshards"] == {"prefill": 1}
    assert s["manifest"]["prefill"] == ["all-gather/dp",
                                        "all-reduce/tp"]
    kinds = [r["kind"] for r in rec.snapshot()]
    assert kinds == ["compile", "compile", "reshard"]
    assert rec.snapshot()[-1]["new_ops"] == [{"op": "all-gather",
                                              "axis": "dp"}]
    labels = {tuple(sorted(lbl.items())): v
              for lbl, v in mm.reshards.items()}
    assert labels == {(("entry", "prefill"),): 1}

    rec.ingest("prefill", (1, 8), [ar])           # shrank: no trip
    assert rec.summary()["reshards"] == {"prefill": 1}


def test_dispatch_totals_and_counter_labels():
    mm = MeshMetrics()
    rec = CollectiveRecorder(metrics=mm)
    rec.ingest("decode_burst", (8, 1), megatron_collectives(
        layers=2, tokens=8, hidden=64, tp=2, dtype_bytes=4))
    per_dispatch = 4 * 2 * (8 * 64 * 4)
    for _ in range(3):
        rec.record_dispatch("decode_burst", (8, 1))
    rec.record_dispatch("unknown_entry", (4,))     # uncached: bytes 0
    s = rec.summary()
    assert s["dispatches"] == 4
    assert s["entries"]["decode_burst"]["bytes_total"] == 3 * per_dispatch
    assert s["entries"]["unknown_entry"]["bytes_total"] == 0
    total = sum(v for _lbl, v in mm.collective_bytes.items())
    assert total == 3 * per_dispatch


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------


def _dev(i, process_index=0, coords=None):
    return SimpleNamespace(id=i, process_index=process_index,
                           coords=coords, platform="tpu")


def test_classify_link_tiers():
    a, b = _dev(0), _dev(1)
    assert classify_link(a, a) == "local"
    assert classify_link(_dev(0), _dev(0)) == "local"     # same id
    assert classify_link(a, b) == "ici"                   # same host
    assert classify_link(a, _dev(2, process_index=1)) == "dcn"
    # two cores of one chip share coords → on-chip
    assert classify_link(_dev(0, coords=(0, 0, 0)),
                         _dev(1, coords=(0, 0, 0))) == "local"
    assert classify_link(_dev(0, coords=(0, 0, 0)),
                         _dev(1, coords=(1, 0, 0))) == "ici"


def test_link_cost_ordering_and_env_override():
    a, b, c = _dev(0), _dev(1), _dev(2, process_index=1)
    assert link_cost(a, a) < link_cost(a, b) < link_cost(a, c)
    bw = link_bandwidths(env={"DYN_LINK_BW_ICI": "1e9"})
    assert bw["ici"] == 1e9
    assert link_cost(a, b, env={"DYN_LINK_BW_ICI": "1e9"}) == 1e-9


def test_link_for_pull_path():
    assert link_for_pull_path("device") == "ici"
    assert link_for_pull_path("plane") == "dcn"
    assert link_for_pull_path("wire") == "dcn"
    assert link_for_pull_path("nonsense") == "?"


def test_topology_summary_census():
    devs = [_dev(0), _dev(1), _dev(2, process_index=1),
            _dev(3, process_index=1)]
    s = topology_summary(devices=devs)
    assert s["n_devices"] == 4 and s["n_processes"] == 2
    # pairs: (0,1) ici, (2,3) ici, 4 cross-process dcn
    assert s["pairs_by_link"] == {"local": 0, "ici": 2, "dcn": 4}
    assert set(s["bandwidth_bytes_per_s"]) == {"local", "ici", "dcn"}


# ---------------------------------------------------------------------------
# fleet / telemetry summaries
# ---------------------------------------------------------------------------


def test_mesh_summary_none_without_series_and_rich_with():
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.telemetry import (
        mesh_summary,
        snapshot_metrics,
    )

    reg = MetricsRegistry()
    mm = MeshMetrics()
    mm.register(reg)
    assert mesh_summary(snapshot_metrics(reg)) is None

    mm.collective_bytes.inc(1024, entry="prefill", op="all-reduce",
                            axis="tp")
    mm.reshards.inc(1, entry="prefill")
    mm.device_bytes.set(100, device="0")
    mm.device_bytes.set(200, device="1")
    mm.skew_ratio.observe(1.33)
    out = mesh_summary(snapshot_metrics(reg))
    assert out["collective_bytes_total"] == 1024
    assert out["bytes_by_entry"] == {"prefill": 1024}
    assert out["bytes_by_axis"] == {"tp": 1024}
    assert out["reshards"] == {"prefill": 1}
    assert out["device_bytes"] == {"0": 100, "1": 200}
    assert out["skew"]["samples"] == 1
