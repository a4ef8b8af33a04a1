"""aiperf-style sweep tool against a live mocker deployment, plus the
load-shape generators (sin/burst/poisson arrivals, prefix-sharing
prompts — reference `benchmarks/sin_load_generator/`,
`benchmarks/prefix_data_generator/`)."""

import json
import os
import random
import subprocess
import sys

from tests.test_http_frontend import setup_stack, teardown_stack

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_arrival_time_shapes():
    from benchmarks.sweep import arrival_times

    rng = random.Random(0)
    po = arrival_times("poisson", 200, qps=10.0, sin_period=30,
                       sin_amplitude=0.8, burst_size=8, rng=rng)
    assert len(po) == 200 and all(b > a for a, b in zip(po, po[1:]))
    # mean rate ~ qps
    assert 10 < po[-1] < 40, po[-1]

    rng = random.Random(0)
    si = arrival_times("sin", 400, qps=10.0, sin_period=10.0,
                       sin_amplitude=0.9, burst_size=8, rng=rng)
    # seasonal shape: per-half-period counts must swing well beyond
    # poisson noise (peak rate 19/s vs trough 1/s)
    import collections
    buckets = collections.Counter(int(t // 5) % 2 for t in si)
    hi, lo = max(buckets.values()), min(buckets.values())
    assert hi > 1.5 * lo, buckets

    rng = random.Random(0)
    bu = arrival_times("burst", 32, qps=8.0, sin_period=30,
                       sin_amplitude=0.8, burst_size=8, rng=rng)
    assert bu[0] == bu[7] == 0.0 and bu[8] == bu[15] == 1.0


def test_prefix_sharing_prompts():
    from benchmarks.sweep import make_prompt

    rng = random.Random(3)
    prompts = [make_prompt(rng, 32, prefix_ratio=0.5, prefix_pool=2,
                           seed=7) for _ in range(16)]
    heads = {" ".join(p.split()[:16]) for p in prompts}
    assert len(heads) == 2          # two shared prefixes, reused
    tails = {" ".join(p.split()[16:]) for p in prompts}
    assert len(tails) == 16         # tails stay distinct
    # disjoint default: no shared heads
    rng = random.Random(3)
    flat = [make_prompt(rng, 32) for _ in range(8)]
    assert len({" ".join(p.split()[:16]) for p in flat}) == 8


async def test_router_affinity_under_shared_prefix_load():
    """KV-router e2e with the sweep's prefix-sharing load: requests
    drawn from 2 shared prefixes over 2 workers must develop per-prefix
    worker affinity (overlap scoring doing its job); the default
    prefix-disjoint load can't."""
    import asyncio

    from benchmarks.sweep import make_prompt
    from dynamo_tpu.router.kv_router import KvPushRouter, KvRouterConfig
    from dynamo_tpu.runtime.context import Context
    from tests.test_kv_router import (
        BS,
        make_request,
        make_rt,
        spawn_mock_worker,
    )

    def tokenize(words: str) -> list[int]:
        # stable word -> id map; shared word-prefixes become shared
        # token-block prefixes (4 blocks of BS for the 64-word head)
        return [(hash(w) & 0x7FFF) + 1 for w in words.split()]

    rt = await make_rt()
    try:
        ns, comp = "ns", "mock"
        e1, _ = await spawn_mock_worker(rt, ns, comp, worker_id=1)
        e2, _ = await spawn_mock_worker(rt, ns, comp, worker_id=2)
        ep = rt.namespace(ns).component(comp).endpoint("generate")
        client = await ep.client()
        kv_push = await KvPushRouter(
            client, rt.events, KvRouterConfig(block_size=BS)).start()
        await client.wait_ready()

        rng = random.Random(11)
        routed: dict[str, list[int]] = {}
        for i in range(12):
            prompt = make_prompt(rng, 96, prefix_ratio=0.67,
                                 prefix_pool=2, seed=5)
            head = " ".join(prompt.split()[:64])
            toks = tokenize(prompt)
            out = [x async for x in kv_push.generate(
                make_request(toks), Context())]
            assert out[-1]["finish_reason"] == "length"
            await asyncio.sleep(0.03)   # let stored events land
            sel = kv_push.router.find_best_match(
                f"probe{i}", toks, update_states=False)
            routed.setdefault(head, []).append(sel.worker[0])
        assert len(routed) == 2
        for head, workers in routed.items():
            # after its first request lands, a prefix's traffic must
            # stick to the worker that cached it
            tail = workers[1:]
            assert tail and max(tail.count(w) for w in set(tail)) \
                == len(tail), routed
        await kv_push.stop()
        await e1.close()
        await e2.close()
    finally:
        await rt.close()


async def test_sweep_levels_against_mocker():
    from benchmarks.sweep import run_level, sweep

    rt, fe, hs, es = await setup_stack()
    try:
        rows = await sweep(fe.url, "mock-model", [1, 4], n_requests=6,
                           isl=24, osl=8)
        assert len(rows) == 2
        for row in rows:
            assert row["errors"] == 0
            assert row["output_tok_s"] > 0
            assert row["ttft_p50_ms"] > 0
            assert row["itl_p50_ms"] >= 0
        # more concurrency must not reduce counted requests
        assert all(r["requests"] == 6 for r in rows)
    finally:
        await teardown_stack(rt, fe, hs, es)


async def test_sweep_open_loop_poisson_with_prefix():
    from benchmarks.sweep import run_level

    rt, fe, hs, es = await setup_stack()
    try:
        row = await run_level(fe.url, "mock-model", 0, n_requests=6,
                              isl=24, osl=8, arrival="poisson",
                              qps=20.0, prefix_ratio=0.5)
        assert row["errors"] == 0
        assert row["arrival"] == "poisson"
        assert row["offered_qps"] > 0
        assert row["prefix_ratio"] == 0.5
    finally:
        await teardown_stack(rt, fe, hs, es)


async def test_sweep_cli_process():
    """The real CLI drives a live frontend and exits 0."""
    rt, fe, hs, es = await setup_stack()
    try:
        import asyncio

        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "benchmarks.sweep",
            "--url", fe.url, "--model", "mock-model",
            "--isl", "16", "--osl", "4", "--concurrency", "2",
            "--requests", "4",
            env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE)
        out, _ = await asyncio.wait_for(proc.communicate(), 120)
        assert proc.returncode == 0, out.decode()
        lines = [json.loads(l) for l in out.decode().splitlines()]
        assert lines[-1]["summary"] == "best_throughput"
        assert lines[0]["concurrency"] == 2 and lines[0]["errors"] == 0
    finally:
        await teardown_stack(rt, fe, hs, es)
