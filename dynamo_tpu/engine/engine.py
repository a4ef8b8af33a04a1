"""TpuEngine: the owned serving engine — continuous batching over jitted
prefill/decode steps with a paged KV cache.

This replaces the reference's engine workers (vLLM/SGLang/TRT-LLM,
`components/src/dynamo/vllm/main.py`): same engine contract as MockEngine —
`PreprocessedRequest` dicts in, `EngineOutput` dict stream out — so the
entire serve path (frontend, router, disagg) is engine-agnostic.

XLA discipline:
- all device shapes are bucketed (prefill length → pow2 chunks, decode
  batch → pow2) so each shape compiles once and is cached
- cache buffers are donated through every step (in-place updates in HBM)
- one device round-trip per decode iteration: decode_step + sample_tokens
  run on device, only the sampled (B,) ints come back to host
- scheduling, stop conditions, paging are host-side (Python), overlapped
  with device work via a single background asyncio task
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import threading
import time
import uuid
from dataclasses import dataclass, field
from functools import partial
from typing import Any, AsyncIterator, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.metrics import EngineMetrics
from dynamo_tpu.engine.pages import (PagePool, kv_block_shape,
                                     kv_layer_shape)
from dynamo_tpu.engine.memory import is_resource_exhausted, record_oom
from dynamo_tpu.engine.profiler import recorder_from_env
from dynamo_tpu.engine.sampling import sample_tokens_lp
from dynamo_tpu.llm.perf import itl_percentile
from dynamo_tpu.engine.attention import ragged_enabled
from dynamo_tpu.models import family_module
from dynamo_tpu.models.llama import (
    LlamaConfig,
    mixed_prefill_decode,
    ragged_prefill_decode,
)
from dynamo_tpu.protocols import (
    DEADLINE_ADMIT_ERR,
    FINISH_CANCELLED,
    FINISH_ERROR,
    FINISH_LENGTH,
    FINISH_STOP,
    EngineOutput,
    ForwardPassMetrics,
    KvCacheEvent,
    KvStats,
    PreprocessedRequest,
    SpecDecodeStats,
    WorkerStats,
)
from dynamo_tpu.runtime.context import ENGINE, WORKER_IN, Context
from dynamo_tpu.runtime.tracing import RequestTrace
from dynamo_tpu.tokens import TokenBlockSequence

logger = logging.getLogger(__name__)

_NO_SPAN = contextlib.nullcontext()


@jax.jit
def _gather_kv_jit(k_cache, v_cache, ids) -> "jax.Array":
    """(2, L, KVH, n, P, D) page gather as one XLA program — from the
    per-layer tuple layout or the pp engines' (L, ...) stacked one."""
    if isinstance(k_cache, tuple):
        k_sel = jnp.stack([kc[:, ids] for kc in k_cache])
        v_sel = jnp.stack([vc[:, ids] for vc in v_cache])
    else:
        k_sel, v_sel = k_cache[:, :, ids], v_cache[:, :, ids]
    return jnp.stack([k_sel, v_sel])


@partial(jax.jit, donate_argnums=(0, 1))
def _write_kv_pages_jit(k_cache, v_cache, ids,
                        data) -> tuple[Any, Any]:
    """Scatter imported (2, L, KVH, n, P, D) data into the paged caches
    at `ids` — one XLA program (the eager per-layer .at[].set form paid
    2L dispatches per disagg import), caches donated so the
    update is in place. Handles BOTH cache layouts: the per-layer
    tuple (plain engines) and the (L, KVH, N, P, D) stacked array (pp
    engines — the old per-layer loop would have silently rebuilt the
    stacked cache as a tuple and corrupted the pp layout)."""
    if isinstance(k_cache, tuple):
        new_k = tuple(
            kc.at[:, ids].set(data[0, l].astype(kc.dtype))
            for l, kc in enumerate(k_cache))
        new_v = tuple(
            vc.at[:, ids].set(data[1, l].astype(vc.dtype))
            for l, vc in enumerate(v_cache))
        return new_k, new_v
    return (k_cache.at[:, :, ids].set(data[0].astype(k_cache.dtype)),
            v_cache.at[:, :, ids].set(data[1].astype(v_cache.dtype)))


@partial(jax.jit, static_argnames=("page_size",), donate_argnums=(0, 1))
def _sp_writeback(k_cache: tuple, v_cache: tuple, k_all, v_all,
                  page_ids, page_size: int) -> tuple[tuple, tuple]:
    """Scatter sequence-parallel prefill KV ((L, T, KVH, D), T page-
    aligned) into the paged caches at `page_ids` ((T/page_size,))."""

    def blocks(a):
        t, kvh, d = a.shape
        b = a.reshape(t // page_size, page_size, kvh, d)
        return jnp.transpose(b, (2, 0, 1, 3))           # (KVH, nP, P, D)

    new_k = tuple(kc.at[:, page_ids].set(blocks(k_all[l]))
                  for l, kc in enumerate(k_cache))
    new_v = tuple(vc.at[:, page_ids].set(blocks(v_all[l]))
                  for l, vc in enumerate(v_cache))
    return new_k, new_v


@jax.jit
def _first_tokens_into(tokens, sampled, take, col) -> "jax.Array":
    """A burst's (B,) input tokens with the lanes in `take` filled from
    a first-token sampler's packed output (row 0 = ids, f32), column
    `col`: on the device, so the burst is launched before the sampler
    is synced."""
    return jnp.where(take, sampled[0, col].astype(jnp.int32), tokens)


def _topk_list(ids_vec, lps_vec, width: int) -> list:
    """[[token_id, logprob], ...] from parallel packed top-k vectors —
    the ONE unpacker for every burst flavor's packed rows (prefill,
    plain/pipelined burst, spec), so a layout change can't silently
    skew one path's alternatives."""
    return [[int(ids_vec[j]), float(lps_vec[j])] for j in range(width)]


def _next_bucket(n: int, lo: int, hi: int, align: int = 1) -> int:
    """Smallest bucket >= n from {lo·2^k, lo·3·2^(k-1)}: pow2-only
    buckets waste up to 50% padding (ISL 96 → 128 pads a third of the
    prefill FLOPs); the 3·2^k sizes cap waste at ~33% while only
    ~doubling the bounded compile count. Mid buckets that are not
    multiples of `align` (the page size) are skipped — a misaligned T
    would silently disable the full-page pallas KV-write kernel and
    cost more than the padding saved. Clamps to [lo, hi]."""
    b = lo
    while b < hi:
        if n <= b:
            return b
        mid = b + b // 2
        if n <= mid <= hi and mid % align == 0:
            return mid
        b *= 2
    return min(b, hi)


def _next_pow2(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return min(b, hi)


@dataclass
class TpuEngineConfig:
    model: LlamaConfig = field(default_factory=LlamaConfig.tiny)
    num_pages: int = 1024                 # incl. scratch page 0
    max_batch_size: int = 8
    prefill_chunk: int = 512              # max tokens per prefill call
    min_prefill_bucket: int = 16
    watermark: float = 0.95
    worker_id: int = 0
    dp_rank: int = 0
    default_max_tokens: int = 1024
    rng_seed: int = 0
    # Fused decode steps per host round-trip: device samples each token and
    # feeds it to the next step; the host syncs once per burst. Critical on
    # TPU where a device→host sync stalls the pipeline.
    decode_steps_per_sync: int = 8
    # Double-buffer plain decode bursts: when the batch is full (no
    # admission possible) burst N+1 is dispatched — its input tokens
    # sliced ON DEVICE from burst N's packed output — before burst N's
    # results are pulled to the host, hiding the device→host sync
    # behind the next burst's compute.
    # Lanes that finish mid-pipeline have their overshoot discarded and
    # their pages released only after the in-flight burst lands.
    pipeline_bursts: bool = True
    # Optional jax.sharding.Mesh ("dp","tp" axes): params/cache are placed
    # with the megatron-pattern specs (engine/sharding.py) and every jitted
    # step runs SPMD over it. One engine = one rank's (sub)mesh; dp ranks
    # each own a disjoint tp submesh (WorkerWithDpRank addressing).
    mesh: Optional[Any] = None
    # Pipeline parallelism (models/llama_pp.py): a 1-D ("pp",) Mesh.
    # The layer stack (weights AND the paged KV cache) shards into
    # contiguous stage slices; prefill pipelines prompt CHUNKS through
    # the stages (pp_prefill_paged) and decode round-robins
    # pp_microbatches lane groups with a psum token mailbox
    # (pp_decode_multi_step). For models whose weights exceed a TP
    # slice's HBM. Requires max_batch_size % pp_microbatches == 0 and
    # pp_microbatches >= the stage count. The FULL sampling matrix
    # rides the pipeline (guided grammars, min_p, penalties,
    # top-logprobs — the constrained head runs on the last stage);
    # only speculative decoding and quantize don't compose with pp
    # yet. Reference serves PP via engine flags:
    # trtllm_utils.py:39,167-170 --pipeline-parallel-size.
    pp_mesh: Optional[Any] = None
    pp_microbatches: int = 2
    # Weight quantization: None (bf16), "int8", or "int4" (per-channel
    # weight-only, engine/quant.py; int4 packs two nibbles per int8 byte
    # — lm_head stays int8 for logit quality). Cuts the decode
    # weight-stream floor 2×/4×; applied device-side with donation after
    # params are placed.
    quantize: Optional[str] = None
    # Speculative decoding (engine/spec.py): a small draft model proposes
    # spec_gamma tokens per iteration, the target verifies them in ONE
    # forward. Must share the target's page geometry (page_size,
    # max_pages_per_seq) — draft caches are indexed by the same page
    # tables. Spec bursts serve ALL sampling configs: greedy and
    # temperature/top-p/top-k/min_p lanes via per-lane Leviathan
    # rejection sampling over each lane's actual filtered distribution,
    # guided-grammar lanes through the DFA mask, and penalty lanes
    # through a tentative-counts chain — a draft engine never falls
    # back to the unfused path for sampling reasons.
    draft_model: Optional[LlamaConfig] = None
    spec_gamma: int = 4
    spec_iters_per_sync: int = 8
    # Sequence-parallel long-prompt prefill (models/llama_sp.py): NOVEL
    # prompts (no cached prefix) whose uncached span exceeds sp_threshold
    # run ring-attention prefill over sp_mesh's "sp" axis; the
    # sequence-sharded KV is paged back into the cache and the tail (plus
    # last-token logits) finishes through the normal chunk loop.
    # Two shapes: a 1-D ("sp",) mesh with mesh=None (weights replicated
    # per ring chip — single-host long context), or a 2-D ("sp","tp")
    # mesh composed with mesh= (weights megatron-sharded over tp,
    # sequence over sp — the multi-host 70B shape; sp_mesh tp size must
    # equal the engine mesh's). sp_threshold=0 disables.
    sp_mesh: Optional[Any] = None
    sp_threshold: int = 0
    # "contiguous" or "zigzag" (balanced causal ring; ~2× less attend
    # work — engine/ring_attention.py)
    sp_layout: str = "contiguous"
    # Optional allowed prefill BATCH widths (ascending). Default None =
    # every pow2 up to max_batch_size. Big models pay a whole-model XLA
    # compile PER prefill shape; restricting to e.g. (1, 8) bounds
    # the compile count at the cost of padded prefill FLOPs for
    # mid-sized rounds.
    prefill_batch_widths: Optional[tuple] = None
    # Token-budgeted interleaved prefill: each scheduler iteration runs
    # at most ONE chunk round spending <= this many prompt tokens (drawn
    # from pending sequences' cursors) instead of prefilling every
    # admitted prompt to completion, so in-flight decode lanes emit
    # tokens BETWEEN a long prompt's chunks and ITL is bounded by one
    # budgeted step. Where the engine shape allows (no draft/pp engine,
    # no constrained decode lane, no burst in flight) the chunk round
    # FUSES with the decode burst in one jitted mixed step
    # (models/llama.py mixed_prefill_decode). 0 = disabled: the legacy
    # phase-alternating scheduler, bit-for-bit.
    prefill_chunk_budget: int = 0
    # Bounded admission skip-ahead for the no-tenancy path: when the
    # waiting head can't get pages, try up to this many requests behind
    # it before giving up the round — a page-starved giant no longer
    # parks smaller admissible work (head-of-line blocking). 0 = exact
    # legacy head-only order, bit-for-bit (pinned by
    # tests/test_tenancy.py). Ignored when DYN_TENANCY arms the fair
    # scheduler, which scans tenant heads instead.
    admit_lookahead: int = 0
    # Block diffusion (a model with `attn_block` = B > 1, SDAR): forwards
    # that denoise a block before the one that commits it (B a multiple of
    # it: each fixes B / steps positions), and which masked positions a
    # forward fixes: "sequential" (the leftmost) or
    # "low_confidence_static" (those whose best token is most probable).
    # Deployment choices, the worker's --dllm-* flags.
    dllm_denoising_steps: int = 0
    dllm_unmasking_strategy: str = "sequential"


@dataclass(eq=False)  # a sequence is itself: `in` / `.remove` scan pointers
class _Seq:
    req: PreprocessedRequest
    ctx: Context
    queue: asyncio.Queue
    token_seq: TokenBlockSequence         # tokens whose KV is on device
    prompt: list[int]                     # effective prompt (incl. replays)
    prompt_hashes: list[int] = field(default_factory=list)
    pages: list[int] = field(default_factory=list)
    # disagg: host KV data to preload into this seq's pages before prefill
    import_kv: Optional[tuple] = None     # (np array (2,L,KVH,n,P,D), len)
    cached_len: int = 0                   # prefix-cache hit length
    # resumable prefill chunk cursor: prompt positions < prefill_pos have
    # target KV on device. Partial-prefill sequences (cursor mid-prompt)
    # stay in _running but are excluded from decode batches — and from
    # draft catch-up and guided first-token handling — until the cursor
    # reaches len(prompt) and `prefilled` flips.
    prefill_pos: int = 0
    last_emit_t: float = 0.0              # monotonic stamp of last emission
    draft_pos: int = 0                    # draft-cache-valid positions < this
    guided: Optional[Any] = None          # GuidedTables when constrained
    guided_state: int = 0                 # authoritative DFA state (host)
    out_counter: dict = field(default_factory=dict)  # token -> emit count
    next_token: int = -1                  # sampled, KV not yet written
    # block diffusion: ids already known at the head of the lane's next
    # block (a prompt's tail past the last whole block), not yet committed
    given: list[int] = field(default_factory=list)
    # the state slot a sequence of a model with recurrent layers owns from
    # admission to its end (engine/pages.py SlotPool); 0: none
    slot: int = 0
    _hist: Optional[tuple] = None         # (len(prompt), (V,) histogram)

    @property
    def wants_topk(self) -> bool:
        """True when this lane asked for top-k alternative logprobs."""
        return self.req.sampling.top_logprobs > 0

    @property
    def needs_constrained(self) -> bool:
        """True when this lane needs the constrained decode burst
        (grammar mask, min_p, or any sampling penalty). Spec bursts
        serve ALL of these (engine/spec.py threads the same masks/
        penalties/filters through draft and verify), so this gates only
        the NON-spec burst choice."""
        return (self.guided is not None
                or self.req.sampling.min_p > 0.0 or self.has_penalties)

    @property
    def has_penalties(self) -> bool:
        sp = self.req.sampling
        return (sp.repetition_penalty != 1.0
                or sp.frequency_penalty != 0.0
                or sp.presence_penalty != 0.0)

    def prompt_hist(self, vocab: int) -> "np.ndarray":
        """Cached (V,) prompt-token histogram for the penalty paths —
        the prompt only changes on preemption (tokens fold in, length
        strictly grows), so length is a sound cache key. Recomputing
        np.unique over a long prompt on EVERY decode burst is host work
        on the critical path."""
        if self._hist is None or self._hist[0] != len(self.prompt):
            ids, cnts = np.unique(
                np.asarray(self.prompt, dtype=np.int64) % vocab,
                return_counts=True)
            arr = np.zeros(vocab, dtype=np.int32)
            arr[ids] = cnts
            self._hist = (len(self.prompt), arr)
        return self._hist[1]
    generated: int = 0                    # sampled tokens streamed
    prefilled: bool = False
    finished: bool = False
    seed: int = 0
    arrival: int = 0
    # lifecycle timestamps (perf_counter for metrics, time_ns for span
    # boundaries, the stage clock) + the trace handle. `trace` is None unless
    # DYN_TRACE is on — every scheduler touch is `if seq.trace is not
    # None`, so disabled tracing allocates nothing on the hot loop.
    t_enqueue: float = 0.0
    t_enqueue_ns: int = 0
    t_admit_ns: int = 0
    t_first_ns: int = 0
    trace: Optional[RequestTrace] = None
    decode_compiled: bool = False         # a decode burst compiled mid-flight
    # tenancy (dynamo_tpu/tenancy): resolved tenant name when DYN_TENANCY
    # is armed, else None — the fair scheduler and per-tenant metrics key
    # off it; untenanted engines never read it
    tenant: Optional[str] = None
    # serving class (dynamo_tpu/serving_classes): resolved class name
    # when DYN_CLASSES is armed, else None — class-weighted fair-share
    # accounting keys off it; classless engines never read it
    cls: Optional[str] = None

    @property
    def pos(self) -> int:
        return len(self.token_seq)

    @property
    def max_tokens(self) -> int:
        return self.req.stop.max_tokens or 0


class TpuEngine:
    """AsyncEngine over a JAX model with paged KV cache."""

    def __init__(self, config: Optional[TpuEngineConfig] = None,
                 params: Optional[dict] = None,
                 event_sink: Optional[Callable[[KvCacheEvent], None]] = None,
                 metrics_sink: Optional[Callable[[ForwardPassMetrics], None]]
                 = None, draft_params: Optional[dict] = None,
                 token_bytes: Optional[list] = None,
                 eos_token_id: int = 0) -> None:
        self.config = config or TpuEngineConfig()
        cfg = self.config
        self.model_cfg = cfg.model
        mcfg = self.model_cfg
        # captured BEFORE the locals are rebound below: quantization may
        # only donate buffers the ENGINE created — caller-provided arrays
        # can be aliased elsewhere (shard_params' device_put is a no-op
        # when the sharding already matches), and donating them destroys
        # the caller's objects
        owned_params = params is None
        owned_draft = draft_params is None
        self._dllm = mcfg.attn_block > 1
        # a model with recurrent layers keeps a per-sequence state beside
        # the pages, in slots; what does not know of it is refused
        self.recurrent = bool(getattr(mcfg, "recurrent", False))
        if self.recurrent:
            self._check_recurrent()
        if getattr(mcfg, "num_experts", 0):
            # MoE serving layouts: single-device, pp_mesh (stage slices
            # carry their experts), an ('ep',) mesh (experts shard,
            # attention + KV cache replicate, GSPMD psums the expert
            # combine), or a 2-D ('ep','tp') mesh (attention
            # additionally megatron-shards over tp — the Mixtral-8x7B
            # multi-host shape). quantize='int8' composes (weight-only
            # expert stacks via mixtral._qe); sp, other mesh axes, and
            # w8a8/int4 experts are rejected loudly below.
            if cfg.sp_mesh is not None:
                raise ValueError(
                    "MoE models don't compose with sp ring prefill "
                    "yet; serve single-device, over pp_mesh, or over "
                    "an ('ep',)/('ep','tp') mesh")
            if cfg.mesh is not None and not (
                    "ep" in cfg.mesh.axis_names
                    and set(cfg.mesh.axis_names) <= {"ep", "tp"}):
                raise ValueError(
                    "an MoE serving mesh must be ('ep',) — experts "
                    "shard over it — or 2-D ('ep','tp') with attention "
                    "megatron-sharded over tp; other axes would "
                    "silently replicate the whole model")
            if cfg.quantize and cfg.quantize != "int8":
                raise ValueError(
                    "MoE expert stacks support weight-only int8 "
                    "(mixtral._qe); w8a8/int4 expert kernels don't "
                    "exist yet")
            if cfg.mesh is not None and cfg.draft_model is not None:
                raise ValueError(
                    "speculative decoding on an ep mesh needs the "
                    "draft placed with family-matched specs (future "
                    "work); drop draft_model or the mesh")
        elif cfg.mesh is not None and "tp" not in cfg.mesh.axis_names:
            # a dense model on an ('ep',)-style mesh would crash deep in
            # param placement with an opaque 'mesh has no axis tp' —
            # reject at the boundary where the cause is stateable
            raise ValueError(
                "dense-family mesh serving shards over 'tp'; an "
                "('ep',) mesh is for MoE models")
        if self._dllm:
            self._check_block_diffusion()
        self.slots = None
        cache_kw = {}
        if self.recurrent:
            from dynamo_tpu.engine.pages import SlotPool

            self.slots = SlotPool(cfg.max_batch_size + 1)
            cache_kw["num_slots"] = self.slots.num_slots
        # the one place the entries are taken: by the configuration's class
        entries = family_module(mcfg)
        init_params, init_cache = entries.init_params, entries.init_cache
        self._prefill_batch = entries.prefill_batch
        self._decode_multi_step = entries.decode_multi_step

        def place_owned(p, owned: bool):
            """Host (numpy) checkpoints must land on device ONCE at
            init: a numpy leaf passed to a jitted step re-uploads on
            EVERY call (jax does not cache host transfers): the whole
            weight set per burst. The
            device copy is engine-owned, so quantization may donate
            it — but only when the caller gave host arrays (device_put
            of an already-device array is a no-op aliasing the
            caller's buffer)."""
            all_host = all(not hasattr(x, "devices")
                           for x in jax.tree.leaves(p))
            return jax.device_put(p), owned or all_host

        if cfg.pp_mesh is not None:
            from jax.sharding import NamedSharding

            from dynamo_tpu.models.llama_pp import (
                pp_cache_specs,
                pp_specs_for,
            )

            n_stages = cfg.pp_mesh.shape["pp"]
            if cfg.mesh is not None or cfg.sp_mesh is not None:
                raise ValueError("pp_mesh does not compose with mesh/"
                                 "sp_mesh (one layout per engine)")
            if cfg.draft_model is not None or cfg.quantize:
                raise ValueError("pp_mesh does not yet support "
                                 "speculative decoding or quantize")
            if cfg.pp_microbatches < n_stages:
                raise ValueError(
                    f"pp_microbatches={cfg.pp_microbatches} must be >= "
                    f"pp stages {n_stages} (the decode mailbox needs a "
                    f"microbatch's token sampled before its next slot)")
            if cfg.max_batch_size % cfg.pp_microbatches:
                raise ValueError("max_batch_size must be divisible by "
                                 "pp_microbatches")
            if mcfg.num_layers % n_stages:
                raise ValueError(f"{mcfg.num_layers} layers not "
                                 f"divisible by pp={n_stages}")
            if params is None:
                params = init_params(jax.random.PRNGKey(cfg.rng_seed),
                                     mcfg)
            self.params = jax.tree.map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(cfg.pp_mesh, s)),
                params, pp_specs_for(params),
                is_leaf=lambda x: not isinstance(x, dict))
            # paged KV stacked (L, KVH, N, P, D), layer axis over pp —
            # each stage holds its slice's pages only
            shape = (mcfg.num_layers,
                     *kv_layer_shape(mcfg, cfg.num_pages))
            mk_cache = jax.jit(
                lambda: jnp.zeros(shape, mcfg.dtype),
                out_shardings=NamedSharding(cfg.pp_mesh,
                                            pp_cache_specs()))
            self.k_cache, self.v_cache = mk_cache(), mk_cache()
        elif cfg.mesh is None:
            if params is None:
                params = init_params(jax.random.PRNGKey(cfg.rng_seed), mcfg)
            else:
                params, owned_params = place_owned(params, owned_params)
            self.params = params
            self.k_cache, self.v_cache = init_cache(mcfg, cfg.num_pages,
                                                    **cache_kw)
        else:
            from dynamo_tpu.engine.sharding import (
                cache_sharding,
                param_sharding,
                shard_params,
            )

            if params is None:
                # init directly sharded (jit + out_shardings): the full
                # parameter set must never materialize on one device — an
                # 8B bf16 model alone would OOM a single v5e chip
                params = jax.jit(
                    lambda key: init_params(key, mcfg),
                    out_shardings=param_sharding(
                        cfg.mesh, mcfg.attention_bias,
                        moe=bool(getattr(mcfg, "num_experts", 0)),
                        qk_norm=mcfg.qk_norm),
                )(jax.random.PRNGKey(cfg.rng_seed))
                self.params = params
            else:
                # externally-loaded (host) weights: place shard-by-shard
                self.params = shard_params(params, cfg.mesh)
            self.k_cache, self.v_cache = jax.jit(
                lambda: init_cache(mcfg, cfg.num_pages),
                out_shardings=cache_sharding(cfg.mesh),
            )()
        self.draft_params = None
        self.dk_cache = self.dv_cache = None
        self._spec_stats = None
        if cfg.draft_model is not None:
            dm = cfg.draft_model
            if (dm.page_size != mcfg.page_size
                    or dm.max_pages_per_seq != mcfg.max_pages_per_seq):
                raise ValueError(
                    "draft model must share the target's page geometry")
            if cfg.spec_gamma < 1 or cfg.spec_iters_per_sync < 1:
                raise ValueError(
                    "spec_gamma and spec_iters_per_sync must be >= 1")
            self._spec_stats = SpecDecodeStats()
            if cfg.mesh is None:
                if draft_params is not None:
                    self.draft_params, owned_draft = place_owned(
                        draft_params, owned_draft)
                else:
                    self.draft_params = init_params(
                        jax.random.PRNGKey(cfg.rng_seed + 1), dm)
                self.dk_cache, self.dv_cache = init_cache(dm, cfg.num_pages)
            else:
                from dynamo_tpu.engine.sharding import (
                    cache_sharding,
                    param_sharding,
                    shard_params,
                )

                if draft_params is None:
                    self.draft_params = jax.jit(
                        lambda key: init_params(key, dm),
                        out_shardings=param_sharding(
                            cfg.mesh, dm.attention_bias),
                    )(jax.random.PRNGKey(cfg.rng_seed + 1))
                else:
                    self.draft_params = shard_params(draft_params, cfg.mesh)
                self.dk_cache, self.dv_cache = jax.jit(
                    lambda: init_cache(dm, cfg.num_pages),
                    out_shardings=cache_sharding(cfg.mesh),
                )()
        if cfg.quantize:
            if cfg.quantize not in ("int8", "w8a8", "int4"):
                raise ValueError(f"unknown quantize mode {cfg.quantize!r}")
            from dynamo_tpu.engine.quant import QTensor, quantize_params_jit

            def pre_quantized(p) -> bool:
                # already-QTensor params must SKIP the jit pass entirely:
                # a non-donated identity jit COPIES the whole pytree on
                # device (no aliasing without donation) — at 8B scale
                # that transient doubles ~9 GB of weights and OOMs the
                # chip
                return isinstance(p.get("lm_head"), QTensor) or any(
                    isinstance(v, QTensor) for v in jax.tree.leaves(
                        p["layers"],
                        is_leaf=lambda x: isinstance(x, QTensor)))

            # donation frees the bf16 buffers, but ONLY when the engine
            # created (or sharded-copied) them — donating caller-provided
            # device arrays would destroy the caller's objects (e.g. a
            # second engine built from the same params)
            def remark_act_bits(p: dict) -> dict:
                # pre-quantized checkpoints skip the jit pass, so the
                # w8a8 marker must be applied HERE or the mode silently
                # serves W8A16 (aux-only rewrap: no device ops). lm_head
                # stays A16 by the same rule quantize_params applies.
                import dataclasses as _dc

                from dynamo_tpu.engine.quant import QUANT_KEYS

                out = dict(p)
                out["layers"] = {
                    k: (_dc.replace(v, act_bits=8)
                        if k in QUANT_KEYS and isinstance(v, QTensor)
                        and v.bits == 8 else v)
                    for k, v in p["layers"].items()
                }
                return out

            if not pre_quantized(self.params):
                self.params = quantize_params_jit(self.params,
                                                  donate=owned_params,
                                                  mode=cfg.quantize)
            elif cfg.quantize == "w8a8":
                self.params = remark_act_bits(self.params)
            if self.draft_params is not None:
                if not pre_quantized(self.draft_params):
                    self.draft_params = quantize_params_jit(
                        self.draft_params, donate=owned_draft,
                        mode=cfg.quantize)
                elif cfg.quantize == "w8a8":
                    self.draft_params = remark_act_bits(self.draft_params)
        self._sp_params = None
        self._sp_tp = None     # "tp" when sp_mesh is 2-D ("sp", "tp")
        if cfg.sp_mesh is not None and cfg.sp_threshold > 0:
            from jax.sharding import NamedSharding, PartitionSpec

            if "tp" in cfg.sp_mesh.shape:
                # 2-D sp×tp: ring prefill with megatron-tp-sharded
                # weights — the multi-host long-context shape (weights
                # don't fit one chip AND prompts don't fit one chip's
                # activation memory). The engine's own mesh keeps
                # serving decode; prefill borrows the wider sp×tp mesh.
                if cfg.mesh is None:
                    raise ValueError(
                        "a 2-D ('sp','tp') sp_mesh requires mesh= (the "
                        "tp-sharded serving mesh); use a 1-D ('sp',) "
                        "mesh for replicated-weight rings")
                eng_tp = dict(cfg.mesh.shape).get("tp", 1)
                if cfg.sp_mesh.shape["tp"] != eng_tp:
                    raise ValueError(
                        f"sp_mesh tp={cfg.sp_mesh.shape['tp']} must "
                        f"match the engine mesh tp={eng_tp} (same "
                        f"per-shard weight layout)")
                from dynamo_tpu.engine.sharding import shard_params

                # specs only name "tp", so the sp axis replicates: each
                # sp row holds the same tp-sharded weight layout the
                # engine mesh uses (on shared devices this is the same
                # bytes; extra sp rows pay the dp-replication cost
                # multi-host serving pays anyway)
                self._sp_params = shard_params(self.params, cfg.sp_mesh)
                self._sp_tp = "tp"
            else:
                if cfg.mesh is not None:
                    raise ValueError(
                        "a 1-D sp_mesh replicates weights; with mesh= "
                        "use a 2-D ('sp','tp') sp_mesh")
                self._sp_params = jax.device_put(
                    self.params,
                    NamedSharding(cfg.sp_mesh, PartitionSpec()))
                # weights must exist ONCE per chip: the single-device
                # step functions reuse the ring's device-0 shard (a view
                # of the same buffer) instead of a second full copy
                self.params = jax.tree.map(
                    lambda a: a.addressable_shards[0].data,
                    self._sp_params)
        self.pool = PagePool(cfg.num_pages, self.model_cfg.page_size,
                             cfg.worker_id, cfg.dp_rank, event_sink)
        self.kvbm = None   # set by kvbm.KvbmManager when attached
        # guided decoding (llm/guided.py): token-bytes map of the serving
        # tokenizer + per-grammar DFA tables, stacked onto the device for
        # the fused guided burst. Slot 0 is the trivial grammar.
        self._guided_vocab = token_bytes
        self._guided_eos = eos_token_id
        self._guided_tables: dict[str, Any] = {}
        self._guided_slots: dict[str, int] = {}
        # spec-key -> refcount for requests between compile and their
        # _waiting.append: eviction must treat these as live or a
        # concurrent compile at the grammar cap could drop a grammar a
        # request is about to use (the later slot lookup would then
        # KeyError inside the scheduler loop)
        self._guided_pending: dict[str, int] = {}
        self._guided_stack = None          # (bits_dev, next_dev)
        self.metrics_sink = metrics_sink
        self._waiting: list[_Seq] = []
        self._running: list[_Seq] = []
        self._arrivals = 0
        self._loop_task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._stopped = False
        self._progress = 0  # scheduler forward-progress token (canary)
        # ONE bookkeeping path (engine/metrics.py): the scheduler
        # observes into these histograms/counters directly; `/metrics`,
        # `_sys.stats` scheduler_stats, and bench all read the same
        # objects. The historical `perf` dict survives as a derived
        # read-only property below. The reference separates prefill/
        # decode phases at the metrics layer too (TTFT vs ITL in aiperf;
        # ForwardPassMetrics prefill/decode queues) — here the split is
        # measured at the source.
        self.metrics = EngineMetrics()
        if self.slots is not None:
            self.metrics.state_slots.set(self.slots.num_slots - 1)
        # Step flight recorder (engine/profiler.py): None unless
        # DYN_STEP_PROFILE is set — every hot-loop touch below is gated
        # on `is not None`, so off means zero allocation and a
        # byte-identical step loop.
        self.step_recorder = recorder_from_env(self.metrics)
        # runtime-resizable bucket rungs (engine/bucketing.py): installed
        # by the flight-control bucket autotuner; None (the default) keeps
        # the static _next_bucket ladder byte-identical. Applied only at
        # the scheduler-loop safe point between dispatches.
        self.bucket_ladder = None
        # KV lifecycle flight recorder (kvbm/lifecycle.py): same
        # contract — None unless DYN_KV_LIFECYCLE, metrics always-on.
        # The pool shares the recorder; KvbmManager picks it up (and
        # hands it to the tier store) when attached.
        from dynamo_tpu.kvbm.lifecycle import KvbmMetrics
        from dynamo_tpu.kvbm.lifecycle import \
            recorder_from_env as kv_recorder_from_env
        self.kv_metrics = KvbmMetrics()
        self.kv_lifecycle = kv_recorder_from_env(self.kv_metrics)
        self.pool.lifecycle = self.kv_lifecycle
        # HBM memory ledger (engine/memory.py): same contract — None
        # unless DYN_MEM_LEDGER, dynamo_memory_* gauges always-on. When
        # armed, every allocation class the engine controls is seeded
        # here; KvbmManager registers its pinned/staged providers when
        # attached; the CompileTracker dispatch sites feed workspace
        # attribution and the triggering-dispatch marker OOM forensics
        # joins on.
        from dynamo_tpu.engine.memory import (MemoryMetrics,
                                              ledger_from_env)
        self.memory_metrics = MemoryMetrics()
        self.memory_ledger = ledger_from_env(self.memory_metrics)
        self._oom = False
        # Mesh & collective flight recorder (engine/collectives.py):
        # same contract — None unless DYN_MESH_RECORDER, the
        # dynamo_collective_* / dynamo_mesh_* metrics always-on. When
        # armed, _mesh_dispatch re-lowers each freshly-compiled
        # (entry, shape) from ShapeDtypeStructs and walks the optimized
        # HLO for collectives (wire bytes per op/mesh axis), checks
        # recompiles against the entry's first-compile manifest
        # (reshard detection), and folds cached per-key bytes into the
        # per-entry comm budget on every dispatch.
        from dynamo_tpu.engine.collectives import (MeshMetrics,
                                                   mesh_recorder_from_env)
        self.mesh_metrics = MeshMetrics()
        self.mesh_recorder = mesh_recorder_from_env(
            self.mesh_metrics, mesh=cfg.mesh)
        # Tenancy plane (dynamo_tpu/tenancy): same off-by-default
        # contract — None unless DYN_TENANCY, in which case _admit
        # drains per-tenant FIFO heads by weighted deficit instead of
        # the single-FIFO head, per-tenant KV budgets cap page
        # occupancy, and dynamo_tenant_* goodput/queue-wait/kv_blocks
        # attribute by the propagated x-dyn-tenant header.
        from dynamo_tpu.tenancy import tenancy_from_env
        self.tenancy = tenancy_from_env()
        self.fair = None
        self.tenant_metrics = None
        if self.tenancy is not None:
            from dynamo_tpu.tenancy import FairScheduler, TenantMetrics
            self.fair = FairScheduler(self.tenancy)
            self.tenant_metrics = TenantMetrics()
        # Serving-class plane (dynamo_tpu/serving_classes): None unless
        # DYN_CLASSES. Class-weighted fair-share rides the same
        # FairScheduler; spec_shrink is the brownout stage-3 actuator —
        # when set, decode bursts fall back to the non-spec compiled
        # variant (no new XLA shapes), freeing draft compute for TTFT.
        from dynamo_tpu.serving_classes import classes_from_env
        self.classes = classes_from_env()
        self.spec_shrink = False
        if self.classes is not None and self.fair is not None:
            self.fair.classes = self.classes
        if self.memory_ledger is not None:
            from dynamo_tpu.models.loader import params_footprint

            self.memory_ledger.set_class(
                "weights", params_footprint(self.params),
                source="models/loader post-load footprint")
            # the recurrent layers' state rides in the cache tuples and
            # is a class of its own
            state_bytes = 0
            if self.recurrent:
                from dynamo_tpu.engine.pages import state_slot_bytes

                state_bytes = self.slots.num_slots * state_slot_bytes(
                    mcfg, jnp.dtype(mcfg.dtype).itemsize)
                self.memory_ledger.set_class(
                    "state_slots", state_bytes,
                    source="engine/pages.py SlotPool x state_slot_bytes")
            # provider, not a frozen number: k/v caches are donated and
            # replaced every step, and quantized KV swaps the dtype
            self.memory_ledger.provider(
                "kv_pool",
                lambda: sum(a.nbytes for a in self.k_cache)
                + sum(a.nbytes for a in self.v_cache) - state_bytes,
                source="engine/pages.py PagePool reservation")
        # raw ITL samples (ms), capped FIFO — bench reads these for
        # exact percentiles; the wire carries only the histogram
        self.itl_samples: list[float] = []
        self._admit_fail_since: Optional[float] = None
        self._rng = np.random.RandomState(cfg.rng_seed)
        # Serializes device access: step functions donate the cache buffers
        # (the pre-step arrays die mid-call), so concurrent readers
        # (kv_pull) must not touch k_cache/v_cache while a step runs.
        self._device_lock = asyncio.Lock()
        # The asyncio lock can't exclude SYNCHRONOUS event-loop code:
        # onboard()'s donating write_kv_pages runs inside _admit with no
        # await, and the KVBM offload worker's gather runs in a thread
        # (holding _device_lock) at the same time — the donation deletes
        # the cache tuple out from under the in-flight gather. This
        # thread lock covers only the two sync cache-buffer entry points
        # (_gather_kv_pages / write_kv_pages); holders never await or
        # take other locks, so it cannot deadlock.
        self._kv_buffer_lock = threading.Lock()
        # decode-burst pipeline state (config.pipeline_bursts): the
        # in-flight burst awaiting its host sync, and — while one is in
        # flight — a redirect for page releases (freeing pages a running
        # burst still writes to would let _admit hand them to a new
        # sequence and corrupt it)
        self._inflight: Optional[dict] = None
        self._defer_releases: Optional[list] = None
        # decoding lanes that ended since the last burst was consumed
        # (their callers, in a closed loop, are on their way back), and
        # the device seconds of the last burst that landed
        self._lanes_ended = 0
        self._burst_s = 0.0
        # rows a real token position sends through the routed expert
        # dispatch (0: a dense model, or experts sharded over 'ep', which
        # keep the dense mask): what dynamo_moe_routed_rows_total counts
        self._routed_per_token = 0
        if getattr(mcfg, "num_experts", 0) and cfg.mesh is None:
            self._routed_per_token = mcfg.experts_per_token * getattr(
                mcfg, "num_moe_layers", mcfg.num_layers)
        # disagg: finished prefill-only sequences whose pages are pinned
        # until the decode worker pulls them (transfer_id -> (pages, len,
        # deadline)); reaped by the scheduler loop after transfer_ttl.
        self._transfers: dict[str, tuple[list[int], int, float]] = {}
        self.transfer_ttl = 60.0

    def _check_recurrent(self) -> None:
        """A model with recurrent layers serves through prefill rounds and
        the plain decode burst on one device. Every subsystem that reads
        "a prefix of tokens is a set of pages" would have to carry the
        state at that boundary too; until it does (state snapshots at
        block boundaries) it is refused at start, each with its reason."""
        cfg = self.config
        if cfg.mesh is not None or cfg.pp_mesh is not None \
                or cfg.sp_mesh is not None:
            raise ValueError(
                "a model with recurrent layers is served on one device: "
                "tp / pp / sp / ep above 1 would split or move a "
                "per-sequence state no mesh entry knows")
        if cfg.draft_model is not None:
            raise ValueError(
                "a model with recurrent layers does not compose with a "
                "draft model: a rejected draft token cannot be taken back "
                "out of the state")
        if self._dllm or cfg.dllm_denoising_steps:
            raise ValueError(
                "a model with recurrent layers is not served by block "
                "diffusion (--dllm-*): a denoising forward would advance "
                "the state it only reads")
        if cfg.prefill_chunk_budget > 0:
            raise ValueError(
                "a model with recurrent layers does not compose with "
                "prefill_chunk_budget: the mixed step has no slots")
        if ragged_enabled():
            raise ValueError(
                "a model with recurrent layers does not compose with the "
                "ragged attention path (DYN_ATTENTION_IMPL=ragged): its "
                "flat rows have no slots")
        logger.warning(
            "model has recurrent layers: prefix reuse is OFF (every prompt "
            "is prefilled whole, no KV event is published, the KV router "
            "falls back to load); KVBM tiers and disaggregated transfers "
            "are refused")

    def refuse_if_recurrent(self, what: str) -> None:
        """For what attaches to an engine after it is built (a KVBM tier, a
        disaggregated role)."""
        if self.recurrent:
            raise ValueError(
                f"a model with recurrent layers does not serve {what}: "
                "the pages of a prefix are not its whole state (state "
                "snapshots at block boundaries would turn this on)")

    def _check_block_diffusion(self) -> None:
        """A block-diffusion engine serves through prefill rounds and the
        block burst only; what does not compose is refused at start, each
        with its reason."""
        cfg, mcfg = self.config, self.model_cfg
        blk, steps = mcfg.attn_block, cfg.dllm_denoising_steps
        if steps < 1 or blk % steps:
            raise ValueError(
                f"dllm_denoising_steps={steps} must divide the block "
                f"length {blk}: each step fixes block / steps positions")
        if cfg.dllm_unmasking_strategy not in (
                "sequential", "low_confidence_static"):
            raise ValueError(
                "dllm_unmasking_strategy must be sequential or "
                "low_confidence_static, not "
                f"{cfg.dllm_unmasking_strategy!r}")
        if not 0 <= mcfg.mask_token_id < mcfg.vocab_size:
            raise ValueError(
                f"block diffusion needs the checkpoint's mask_token_id "
                f"inside the vocabulary, not {mcfg.mask_token_id}")
        for size, what in ((mcfg.page_size, "page size"),
                           (cfg.prefill_chunk, "prefill_chunk"),
                           (cfg.decode_steps_per_sync,
                            "decode_steps_per_sync")):
            if size % blk:
                raise ValueError(
                    f"{what} {size} must be a multiple of the block "
                    f"length {blk}: pages, chunks and bursts hold whole "
                    "blocks")
        if cfg.draft_model is not None:
            raise ValueError(
                "block diffusion does not compose with a draft model: a "
                "block step already yields several tokens a forward")
        if cfg.pp_mesh is not None or cfg.sp_mesh is not None:
            raise ValueError(
                "block diffusion is not served over pp / sp meshes: "
                "their prefill and decode entries know one token a step")
        if cfg.prefill_chunk_budget > 0:
            raise ValueError(
                "block diffusion does not compose with "
                "prefill_chunk_budget: the mixed step fuses a chunk with "
                "a one-token decode step")
        if ragged_enabled():
            raise ValueError(
                "block diffusion does not compose with the ragged "
                "attention path (DYN_ATTENTION_IMPL=ragged): its rows "
                "are causal by token")

    @property
    def perf(self) -> dict:
        """Legacy cumulative-counter view, DERIVED from `self.metrics`
        (one source of truth): snapshot with `dict(eng.perf)` and delta
        as before. Writes to the returned dict are discarded — the
        scheduler observes into `self.metrics` directly."""
        return self.metrics.perf_view()

    @property
    def _burst_lookahead(self) -> int:
        """Worst-case positions a single decode burst advances past the
        admitted prompt+max_tokens — the admission guard must budget the
        LARGER of the normal and spec burst shapes, or near-max-context
        requests overflow max_pages_per_seq mid-decode."""
        cfg = self.config
        la = cfg.decode_steps_per_sync
        if self._dllm:
            return la          # whole blocks, one burst at a time
        if cfg.pipeline_bursts:
            la = 2 * cfg.decode_steps_per_sync   # one burst in flight
        if cfg.draft_model is not None:
            la = max(la, cfg.spec_iters_per_sync * (cfg.spec_gamma + 1))
        return la

    # -- engine contract ----------------------------------------------------

    async def generate(self, request: dict, context: Context
                       ) -> AsyncIterator[dict]:
        req = PreprocessedRequest.from_dict(request)
        if req.stop.max_tokens is None:
            req.stop.max_tokens = self.config.default_max_tokens
        cfg, mcfg = self.config, self.model_cfg
        if self._stopped:
            yield EngineOutput(
                token_ids=[], finish_reason=FINISH_ERROR,
                extra={"error": "engine closed"}).to_dict()
            return
        if not req.token_ids:
            yield EngineOutput(
                token_ids=[], finish_reason=FINISH_ERROR,
                extra={"error": "empty prompt"}).to_dict()
            return
        if self._dllm:
            sp = req.sampling
            refused = (
                "guided decoding" if sp.guided
                else "top_logprobs alternatives" if sp.top_logprobs > 0
                else "min_p and sampling penalties" if (
                    sp.min_p > 0.0 or sp.repetition_penalty != 1.0
                    or sp.frequency_penalty != 0.0
                    or sp.presence_penalty != 0.0)
                else "a KV import or export" if req.kv_transfer_params
                else "embeddings" if req.extra.get("embed") else None)
            if refused:
                yield EngineOutput(
                    token_ids=[], finish_reason=FINISH_ERROR,
                    extra={"error": f"block diffusion does not serve "
                                    f"{refused}"}).to_dict()
                return
        if self.recurrent:
            sp = req.sampling
            refused = (
                "guided decoding" if sp.guided
                else "min_p and sampling penalties" if (
                    sp.min_p > 0.0 or sp.repetition_penalty != 1.0
                    or sp.frequency_penalty != 0.0
                    or sp.presence_penalty != 0.0)
                else "a KV import or export" if req.kv_transfer_params
                else "embeddings" if req.extra.get("embed") else None)
            if refused:
                yield EngineOutput(
                    token_ids=[], finish_reason=FINISH_ERROR,
                    extra={"error": "a model with recurrent layers does "
                                    f"not serve {refused}"}).to_dict()
                return
        guided_tables = None
        guided_key = None
        if req.sampling.guided:
            if len(req.stop.stop_token_ids or []) > self.GUIDED_STOP_WIDTH:
                yield EngineOutput(
                    token_ids=[], finish_reason=FINISH_ERROR,
                    extra={"error": f"guided decoding supports at most "
                                    f"{self.GUIDED_STOP_WIDTH} stop "
                                    f"token ids"}).to_dict()
                return
            guided_key = self._guided_key(req.sampling.guided)
            # hold a pending ref across the compile await so a concurrent
            # compile's eviction can't drop this grammar before the seq
            # reaches _waiting (released in the finally below — which also
            # covers CancelledError, a BaseException, at any await)
            self._guided_pending[guided_key] = \
                self._guided_pending.get(guided_key, 0) + 1
        try:
            if guided_key is not None:
                try:
                    guided_tables = await self._compile_guided(
                        req.sampling.guided, req)
                except Exception as e:
                    yield EngineOutput(
                        token_ids=[], finish_reason=FINISH_ERROR,
                        extra={"error": f"guided decoding: {e}"}).to_dict()
                    return
            if req.extra.get("embed"):
                max_ctx = mcfg.page_size * mcfg.max_pages_per_seq
                if len(req.token_ids) > max_ctx:
                    # must reject BEFORE the dense T^2 forward: an unbounded
                    # prompt would compile/allocate under the device lock
                    yield EngineOutput(
                        token_ids=[], finish_reason=FINISH_ERROR,
                        extra={"error": f"embed input ({len(req.token_ids)} "
                                        f"tokens) exceeds context {max_ctx}"}
                    ).to_dict()
                    return
                yield await self._embed_one(req)
                return
            # decode bursts may overshoot by up to one burst's lookahead
            lookahead = self._burst_lookahead
            max_len = mcfg.page_size * mcfg.max_pages_per_seq - lookahead
            need_pages = (len(req.token_ids) + req.stop.max_tokens
                          + lookahead
                          + mcfg.page_size - 1) // mcfg.page_size
            if len(req.token_ids) + req.stop.max_tokens > max_len \
                    or need_pages > self.pool.capacity:
                yield EngineOutput(
                    token_ids=[], finish_reason=FINISH_ERROR,
                    extra={"error": f"prompt+max_tokens exceeds capacity "
                                    f"(context {max_len}, "
                                    f"pages {self.pool.capacity})"}).to_dict()
                return
            ktp = req.kv_transfer_params or {}
            import_kv = None
            if ktp.get("kv_data") is not None:
                data = ktp["kv_data"]
                plen = int(ktp["prefill_len"])
                n_pages = (plen + mcfg.page_size - 1) // mcfg.page_size
                want = kv_block_shape(mcfg, n_pages)
                if not (0 < plen < len(req.token_ids)) \
                        or tuple(data.shape) != want:
                    # a malformed import must fail THIS request, not reach
                    # prefill_all where an exception would _fail_all everyone
                    yield EngineOutput(
                        token_ids=[], finish_reason=FINISH_ERROR,
                        extra={"error": f"bad kv import: prefill_len={plen}, "
                                        f"shape={tuple(data.shape)} != {want}"}
                    ).to_dict()
                    return
                import_kv = (data, plen)
            # trace root parented to the transport serve span (remote:
            # ctx.headers traceparent) or the caller task's current span
            # (in-proc fast path). None when DYN_TRACE is off — the
            # scheduler never allocates a span for untraced requests.
            attrs = {"request.id": context.request_id,
                     "engine.worker_id": cfg.worker_id}
            tenant = None
            if self.tenancy is not None:
                tenant = self.tenancy.tenant_of(
                    getattr(context, "headers", None))
                attrs["tenant"] = tenant
            cls = None
            if self.classes is not None:
                cls = self.classes.class_of(
                    getattr(context, "headers", None))
                attrs["class"] = cls
            trace = RequestTrace.begin(
                "engine.request", getattr(context, "headers", None),
                attrs)
            seq = _Seq(
                req=req, ctx=context, queue=asyncio.Queue(),
                token_seq=TokenBlockSequence(mcfg.page_size),
                prompt=list(req.token_ids),
                prompt_hashes=TokenBlockSequence(
                    mcfg.page_size, req.token_ids).seq_hashes(),
                import_kv=import_kv,
                guided=guided_tables,
                seed=(req.sampling.seed if req.sampling.seed is not None
                      else int(self._rng.randint(0, 2**31 - 1))),
                arrival=self._arrivals,
                t_enqueue=time.perf_counter(),
                t_enqueue_ns=context.stamp(WORKER_IN),
                trace=trace,
                tenant=tenant,
                cls=cls,
            )
            if trace is not None:
                trace.event("enqueued", waiting=len(self._waiting),
                            running=len(self._running),
                            prompt_tokens=len(req.token_ids))
            self._arrivals += 1
            self._ensure_loop()
            self._waiting.append(seq)
            self._wake.set()
            while True:
                out = await seq.queue.get()
                if out is None:
                    return
                yield out
                if out.get("finish_reason"):
                    return
        finally:
            # the pending ref pins the grammar for the request's
            # whole life (covers CancelledError at any await and
            # every early return; once the seq is in _waiting the
            # active-set scan covers it too, so the extra pin is
            # merely redundant, never wrong)
            if guided_key is not None:
                self._guided_unpend(guided_key)

    async def _embed_one(self, req) -> dict:
        """Mean-pooled prompt embedding (llama.embed_batch): a dense
        cache-free forward, bucketed to pow2 lengths so compiles stay
        bounded; runs under the device lock like every device op."""
        from dynamo_tpu.models.llama import embed_batch

        ids = req.token_ids
        t_bucket = _next_pow2(len(ids), self.config.min_prefill_bucket,
                              1 << 30)
        toks = np.zeros((1, t_bucket), dtype=np.int32)
        toks[0, :len(ids)] = ids
        lengths = np.asarray([len(ids)], dtype=np.int32)

        async with self._device_lock:
            def run():
                vec = embed_batch(self.params, jax.numpy.asarray(toks),
                                  jax.numpy.asarray(lengths),
                                  self.model_cfg)
                return np.asarray(vec[0], dtype=np.float32)

            vec = await asyncio.to_thread(run)
        return {"embedding": vec.tolist(), "token_ids": [],
                "finish_reason": FINISH_STOP}

    def clear_kv_blocks(self) -> int:
        """Drop the reusable prefix cache (admin route analog of
        `service/clear_kv_blocks.rs`). Returns pages freed."""
        return self.pool.clear_inactive()

    def device_report(self) -> dict:
        """Where this engine's arrays actually sit — read off the
        weights and the KV cache themselves, not `jax.devices()`: a
        backend that fell back, or a mesh that put every shard on the
        first chip, shows here. `attention_kernels` is the trace-time
        kernel switch the step functions will see."""
        from dynamo_tpu.engine import attention

        by_device: dict = {}
        for leaf in jax.tree.leaves((self.params, self.k_cache,
                                     self.v_cache)):
            for sh in getattr(leaf, "addressable_shards", ()):
                by_device[sh.device] = (by_device.get(sh.device, 0)
                                        + sh.data.nbytes)
        devs = sorted(by_device, key=lambda d: d.id)
        return {
            "platform": devs[0].platform if devs else "none",
            "kind": devs[0].device_kind if devs else "none",
            "count": len(devs),
            "bytes_by_device": {str(d.id): by_device[d] for d in devs},
            "attention_kernels": bool(attention.use_pallas()),
        }

    def progress_token(self) -> int:
        """Monotonic scheduler forward-progress marker. The canary uses it
        to distinguish saturated (token advances while the probe waits —
        don't kill the worker) from wedged (frozen)."""
        return self._progress

    async def close(self) -> None:
        self._stopped = True
        self._wake.set()
        if self._loop_task is not None:
            self._loop_task.cancel()
        self._drain_inflight_sync()
        if self.kvbm is not None:
            # stop the offload/prefetch pipeline and release any
            # pending-offload pins before freeing sequences below
            await self.kvbm.close()
        # unblock any generate() caller still awaiting its queue
        for s in self._running + self._waiting:
            if s.trace is not None:
                s.trace.end(status="ERROR",
                            finish_reason=FINISH_CANCELLED)
            s.queue.put_nowait(EngineOutput(
                token_ids=[], finish_reason=FINISH_CANCELLED).to_dict())
            s.queue.put_nowait(None)
            self.pool.release_sequence(s.pages)
            self._give_slot(s)
        self._running.clear()
        self._waiting.clear()

    # -- scheduler loop -----------------------------------------------------

    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(
                self._scheduler_loop())

    async def _scheduler_loop(self) -> None:
        while not self._stopped:
            if not self._waiting and not self._running:
                if self._inflight is not None:
                    # the last lane finished (stop token) while a
                    # speculative burst was in flight: land it NOW, or
                    # its deferred pages sit out of the pool (and
                    # metrics report stale usage) for the whole idle
                    # period — common at low concurrency since partial
                    # batches pipeline
                    await asyncio.to_thread(self._drain_inflight_sync)
                    continue
                self._wake.clear()
                rec = self.step_recorder
                t_wait = rec.begin("wait") if rec is not None else 0.0
                if self._transfers:
                    # stay reap-able: pinned transfers must expire even
                    # when no requests are in flight
                    try:
                        await asyncio.wait_for(self._wake.wait(), 1.0)
                    except asyncio.TimeoutError:
                        pass
                    self._reap_transfers()
                else:
                    await self._wake.wait()
                if rec is not None:
                    rec.end("wait", t_wait)
                continue
            try:
                with self._span("admit"):
                    if self.bucket_ladder is not None:
                        # safe point: between dispatches, before this
                        # iteration picks its batch shapes
                        self.bucket_ladder.maybe_apply()
                    self._reap_transfers()
                    self._admit()
                    if self.kvbm is not None and self._waiting:
                        # stage tier blocks for still-queued requests
                        # so their admission onboard is one device
                        # write (no-op unless kvbm prefetch_blocks >
                        # 0); router prefix hints (request
                        # extra.kv_hints) ride along
                        hints = [s.req.extra.get("kv_hints")
                                 for s in self._waiting]
                        self.kvbm.prefetch_waiting(
                            self._waiting,
                            hints=[h for h in hints if h] or None)
                if self.kvbm is not None and self.kvbm.remote is not None:
                    # G4: continue freshly-admitted prompts' block chains
                    # from peer workers' tiers before prefill. Fetches
                    # run CONCURRENTLY so the worst-case admission stall
                    # is one fetch_timeout per wave, not per sequence
                    # (onboard_remote never raises)
                    fresh = [s for s in self._running
                             if not s.prefilled and s.import_kv is None
                             and s.prefill_pos <= s.cached_len]
                    if fresh:
                        await asyncio.gather(
                            *(self.kvbm.onboard_remote(s) for s in fresh))
                t0 = time.perf_counter()
                if self._dllm:
                    progressed = await self._prefill_blocks()
                elif self.config.prefill_chunk_budget > 0:
                    progressed = await self._prefill_budgeted()
                else:
                    progressed = await self._prefill_pending()
                t1 = time.perf_counter()
                if progressed:
                    self.metrics.prefill_seconds.inc(t1 - t0)
                decoded = await self._decode_iter()
                if decoded:
                    self.metrics.decode_seconds.inc(
                        time.perf_counter() - t1)
                progressed |= decoded
                with self._span("publish"):
                    self._publish_metrics()
                if progressed:
                    self._progress += 1
                else:
                    rec = self.step_recorder
                    t_yield = rec.begin("yield") if rec is not None else 0.0
                    await asyncio.sleep(0.001)
                    if rec is not None:
                        rec.end("yield", t_yield)
            except Exception as exc:
                led = self.memory_ledger
                if led is not None and is_resource_exhausted(exc):
                    # OOM forensics (engine/memory.py): dump the ledger
                    # ring + step tail + triggering dispatch to a crash
                    # file; exits rc 45 when DYN_OOM_EXIT is armed
                    record_oom(self, exc)
                logger.exception("engine scheduler iteration failed")
                self._fail_all()

    def _drain_inflight_sync(self) -> None:
        """Tear down the decode-burst pipeline: BLOCK until the in-flight
        burst's device writes land (releasing its lanes' pages earlier
        would let a new sequence be corrupted by the still-running
        burst), then free the deferred pages. Error/shutdown paths only."""
        inf, self._inflight = self._inflight, None
        if inf is None:
            return
        try:
            np.asarray(inf["packed"])
        except Exception:
            pass  # the burst itself failed; nothing is writing anymore
        for pages in inf["deferred"]:
            self.pool.release_sequence(pages)

    def _fail_all(self) -> None:
        self._drain_inflight_sync()
        for s in self._running + self._waiting:
            if s.trace is not None:
                s.trace.end(status="ERROR", finish_reason=FINISH_ERROR)
            s.queue.put_nowait(EngineOutput(
                token_ids=[], finish_reason=FINISH_ERROR,
                extra={"error": "engine step failed"}).to_dict())
            s.queue.put_nowait(None)
            self.pool.release_sequence(s.pages)
            self._give_slot(s)
        self._running.clear()
        self._waiting.clear()

    # -- admission ----------------------------------------------------------

    # how long admission may keep failing with offload pins outstanding
    # before the queued batches are force-drained inline. Must comfortably
    # exceed a healthy worker's gather+demote latency INCLUDING its wait
    # for the device lock behind in-flight decode bursts — an iteration
    # count would not: an otherwise-idle scheduler loop burns iterations
    # far faster than the worker's to_thread gather can land, and an
    # early flush degrades every deficit eviction to the inline copy
    _ADMIT_FLUSH_GRACE_S = 0.25

    def _alloc_admission(self, hashes, prompt_len: int):
        """allocate_sequence with a pinned-page escape hatch.

        A failed allocation with offload pins outstanding is NORMAL in
        pipelined mode — the evicted victims are pinned until the
        worker's gather lands, so the caller is expected to retry next
        scheduler iteration. But if it KEEPS failing past the grace
        period (worker stuck on a slow tier, or wedged entirely), the
        pins are HBM the allocator needs: the queued-but-unclaimed
        batches are drained inline, their pins recycle, and the
        allocation is retried. Batches the worker already claimed stay
        with it, so a wedged worker strands at most one drain round."""
        alloc = self.pool.allocate_sequence(hashes, prompt_len)
        if alloc is not None:
            self._admit_fail_since = None
            return alloc
        if self.kvbm is not None and self.pool.pending_offload_pages:
            now = time.monotonic()
            if self._admit_fail_since is None:
                self._admit_fail_since = now
            elif (now - self._admit_fail_since >= self._ADMIT_FLUSH_GRACE_S
                    and self.kvbm.flush_queued_offloads()):
                self._admit_fail_since = None
                alloc = self.pool.allocate_sequence(hashes, prompt_len)
        return alloc

    def _admission_order(self) -> list[int]:
        """Candidate indexes into _waiting for one admission round.
        Legacy (no tenancy, admit_lookahead=0): the head only — the
        exact FIFO order this engine has always run, bit-for-bit.
        admit_lookahead=N: the head plus up to N requests behind it,
        so a page-starved giant can't park smaller admissible work.
        Fair scheduler armed: one index per backlogged tenant (its
        FIFO head), least weighted service first."""
        if self.fair is not None:
            return self.fair.candidate_indexes(
                [s.tenant for s in self._waiting])
        la = self.config.admit_lookahead
        if la > 0:
            return list(range(min(la + 1, len(self._waiting))))
        return [0]

    def _tenant_pages(self, tenant: Optional[str]) -> int:
        """KV pages currently held by a tenant's running sequences."""
        return sum(len(s.pages) for s in self._running
                   if s.tenant == tenant)

    def _admit_one(self) -> bool:
        """Try one admission round over the candidate order; True when
        the outer loop should keep going (admitted, or a cancelled
        entry was reaped), False when nothing is admissible."""
        cfg = self.config
        for idx in self._admission_order():
            cand = self._waiting[idx]
            if cand.ctx.is_cancelled():
                self._waiting.pop(idx)
                self._finish(cand, FINISH_CANCELLED)
                return True
            # A request whose deadline already passed while queued must
            # not burn prefill: drop it here with a distinct in-band
            # error. FINISH_ERROR arrives over a healthy stream — no
            # ConnectionError — so the frontend breaker/replay machinery
            # is naturally skipped (the request failed, the worker
            # didn't).
            deadline = cand.ctx.deadline
            if deadline is not None \
                    and asyncio.get_running_loop().time() >= deadline:
                self._waiting.pop(idx)
                cand.queue.put_nowait(EngineOutput(
                    token_ids=[], finish_reason=FINISH_ERROR,
                    extra={"error": DEADLINE_ADMIT_ERR}).to_dict())
                self._finish(cand, FINISH_ERROR, emit=False)
                return True
            hashes = cand.prompt_hashes
            need_pages = (len(cand.prompt) + self.model_cfg.page_size - 1) \
                // self.model_cfg.page_size
            if self.fair is not None:
                # per-tenant KV budget nets into the admission check:
                # a tenant at its page budget is not admissible this
                # round, but other tenants' heads still are
                budget = self.tenancy.get(cand.tenant).kv_block_budget
                if (budget > 0 and self._running
                        and self._tenant_pages(cand.tenant) + need_pages
                        > budget):
                    continue
            # pinned pages are HBM-occupied but free themselves without
            # any sequence finishing (the offload worker's gather lands);
            # netting them out keeps the watermark from refusing
            # admissions the pipeline will unblock in a step or two
            occupied = self.pool.active_pages - self.pool.pending_offload_pages
            if (occupied + need_pages
                    > cfg.watermark * self.pool.capacity and self._running):
                continue
            t_adm = time.perf_counter()
            if self.recurrent or cand.import_kv is not None:
                # fresh pages only, without hashes. Disagg import: remote
                # KV overwrites them and cached_len comes from the
                # transfer, not hashing. A model with recurrent layers: no
                # prefix reuse, and the slot the sequence keeps until its
                # pages go
                alloc = self._alloc_admission([], len(cand.prompt))
                if alloc is None:
                    self.metrics.admission_stall.observe(
                        time.perf_counter() - t_adm)
                    continue
                cand.pages = alloc[0]
                cand.cached_len = 0 if self.recurrent else cand.import_kv[1]
                if self.recurrent:
                    cand.slot = self.slots.take()
                    self.metrics.state_slots_in_use.set(self.slots.in_use)
            else:
                alloc = self._alloc_admission(hashes, len(cand.prompt))
                if alloc is None:
                    self.metrics.admission_stall.observe(
                        time.perf_counter() - t_adm)
                    continue
                cand.pages, cand.cached_len = alloc
                if self.kvbm is not None:
                    # KVBM onboard: blocks past the device prefix hit that
                    # live in the host/disk tiers are DMA'd into the fresh
                    # pages so prefill skips them
                    cand.cached_len = self.kvbm.onboard(cand)
            # allocation covers any inline eviction gathers; onboard
            # covers tier reads + the device write — both shrink when
            # the async pipeline stages them ahead of time
            self.metrics.admission_stall.observe(
                time.perf_counter() - t_adm)
            wait_s = max(time.perf_counter() - cand.t_enqueue, 0.0)
            self.metrics.queue_wait.observe(wait_s)
            if self.fair is not None:
                self.fair.on_admit(
                    cand.tenant, len(cand.prompt) + cand.max_tokens,
                    cls=cand.cls)
                tm = self.tenant_metrics
                if tm is not None and cand.tenant is not None:
                    tm.observe_queue_wait(cand.tenant, wait_s)
                    tm.kv_blocks.set(
                        self._tenant_pages(cand.tenant) + len(cand.pages),
                        tenant=cand.tenant)
            if cand.trace is not None:
                now_ns = time.time_ns()
                cand.trace.stage(
                    "engine.queue_wait", cand.t_enqueue_ns, now_ns,
                    cached_len=cand.cached_len,
                    prompt_tokens=len(cand.prompt))
                cand.trace.event("admitted",
                                 running=len(self._running) + 1)
                cand.t_admit_ns = now_ns
            # budgeted prefill resumes from here; legacy prefill keys its
            # offsets off cached_len directly and ignores the cursor
            cand.prefill_pos = cand.cached_len
            self._waiting.pop(idx)
            self._running.append(cand)
            return True
        return False

    def _admit(self) -> None:
        cfg = self.config
        while self._waiting and len(self._running) < cfg.max_batch_size:
            if not self._admit_one():
                break

    # -- prefill ------------------------------------------------------------

    async def _prefill_pending(self) -> bool:
        """Prefill every admitted-but-unprefilled sequence with BATCHED
        chunk rounds (prefill_batch): each round streams the weights once
        for the sequences in it. Every round is launched before any is
        waited for. A sequence's first token is sampled behind the round
        that ends ITS prompt, not behind the batch's last round, and
        prompts of more than one chunk take their rounds one after the
        other (_chunk_rounds), so a prompt waits for those ahead of it
        and not for those behind; sequences that end in one round share
        one device call + ONE host sync, so a batch of one-chunk prompts
        is one round and one group. Where the lanes allow it the next
        decode burst is launched behind the samplers before any of them
        is synced (_chain_burst): the first tokens reach the host while
        it runs."""
        inf = self._inflight
        if (inf is not None and self._refills_behind_burst
                and await self._prefill_behind(inf)):
            return True
        pending = self._unprefilled(inf["firsts"] if inf else ())
        if not pending:
            return False
        async with self._device_lock:
            await self._land_first_tokens(
                await self._launch_prefill(pending))
        return True

    def _unprefilled(self, firsts) -> list[_Seq]:
        """Admitted sequences whose prefill is still to be launched:
        not those of `firsts`, launched and not yet landed."""
        launched = {id(s) for group, _, _ in firsts for s in group}
        return [s for s in self._running
                if not s.prefilled and id(s) not in launched]

    async def _prefill_behind(self, inf: dict) -> int:
        """Launch the prefill of everyone admitted and not yet
        prefilled behind the dense burst in flight `inf`, and leave the
        first tokens on the device (`inf["firsts"]`: _chain_burst has to
        wait for the burst, and the scheduler stays awake meanwhile);
        _pipeline_consume lands them with the burst. Returns how many
        sequences that was: 0 where a lane _chain_burst would refuse is
        among them, and that wave keeps the loop's order."""
        pending = self._unprefilled(inf["firsts"])
        if not pending or any(s.needs_constrained for s in pending):
            return 0
        async with self._device_lock:
            inf["firsts"] += await self._launch_prefill(pending)
        return len(pending)

    @property
    def _by_sequence(self) -> bool:
        """Rounds go by sequence, a first token sampled behind the round
        that ends its prompt: every engine but a draft or pp one, which
        needs the whole batch first."""
        return self.draft_params is None and self.config.pp_mesh is None

    @property
    def _refills_behind_burst(self) -> bool:
        """A dense burst in flight is waited for with the scheduler
        awake (_land_burst) and refilled behind (_prefill_behind) where
        a refill is _prefill_pending's by-sequence rounds and nothing
        else: not with a draft, a pp or sp mesh, a chunk budget or the
        ragged round."""
        return (self._by_sequence and self._sp_params is None
                and self.config.prefill_chunk_budget <= 0
                and not self._ragged_active())

    async def _launch_prefill(self, pending: list[_Seq]) -> list:
        """_prefill_pending's first half: launch every round of `pending`
        and the first-token samplers behind them, and wait for none.
        Returns `firsts`: (sequences, sampled on the device, tk) a group,
        in launch order, for _land_first_tokens. Call under the device
        lock."""
        mcfg, cfg = self.model_cfg, self.config
        firsts: list[tuple[list[_Seq], Any, int]] = []
        by_sequence = self._by_sequence

        def sample_early(done):
            group = [s for s in pending if id(s) in done]
            firsts.append((group, *self._first_token_dispatch(group, done)))

        def run_chunks(params_, model_cfg, kc, vc, offsets, on_done=None):
            return self._chunk_rounds(
                params_, model_cfg, kc, vc, pending, offsets,
                tokens_of=lambda s: s.prompt,
                target_len_of=lambda s: len(s.prompt), on_done=on_done)

        def prefill_all():
            for seq in pending:
                if seq.import_kv is not None:
                    data, n_tok = seq.import_kv
                    n_pages = (n_tok + mcfg.page_size - 1) // mcfg.page_size
                    self.write_kv_pages(seq.pages[:n_pages], data)
                    seq.import_kv = None
            offsets = {id(s): s.cached_len for s in pending}
            if self._sp_params is not None:
                self._sp_bulk_prefill(pending, offsets)
            if cfg.pp_mesh is not None:
                self.k_cache, self.v_cache, last_logits = \
                    self._pp_prefill_all(pending, offsets)
            else:
                self.k_cache, self.v_cache, last_logits = run_chunks(
                    self.params, mcfg, self.k_cache, self.v_cache,
                    offsets, sample_early if by_sequence else None)
            if self.draft_params is not None:
                # the draft's paged cache must hold the prompt KV too —
                # over the FULL prompt, never trusting the cached prefix:
                # prefix pages can carry target-only KV (disagg imports,
                # KVBM onboarding, pages registered during non-spec
                # fallback bursts). Recomputing is cheap — the draft is
                # small by construction — and rewriting shared pages is
                # idempotent (same tokens ⇒ same values).
                d_offsets = {id(s): 0 for s in pending}
                self.dk_cache, self.dv_cache, _ = run_chunks(
                    self.draft_params, self.config.draft_model,
                    self.dk_cache, self.dv_cache, d_offsets)
            early = {id(s) for group, _, _ in firsts for s in group}
            rest = [s for s in pending if id(s) not in early]
            firsts.append(
                (rest, *self._first_token_dispatch(rest, last_logits)))

        self.metrics.prefill_new_tokens.inc(sum(
            max(len(s.prompt) - s.cached_len, 0) for s in pending))
        await asyncio.to_thread(prefill_all)
        return firsts

    async def _land_first_tokens(self, firsts: list,
                                 chain: bool = True) -> None:
        """_prefill_pending's second half: chain the next burst behind
        the samplers where the lanes allow it and no burst is in flight
        (and the caller lets it: `chain`), then one host sync and one
        emission a group. Call under the device lock."""
        chained = (chain and self._by_sequence
                   and await self._chain_burst(firsts))
        # a lane its first token ends is overshoot in the chained
        # burst, which still writes to its pages
        deferred = self._inflight["deferred"] if chained else None
        for group, sampled, tk in firsts:
            # ONE host sync a group; later rounds are still running
            packed = await asyncio.to_thread(self._host_sync, sampled)
            self._defer_releases = deferred
            try:
                self._emit_first_tokens(group, packed, tk, draft_done=True)
            finally:
                self._defer_releases = None
        if chained:
            self._inflight["t0"] = time.perf_counter()

    async def _chain_burst(self, firsts: list) -> bool:
        """Launch the decode burst that follows a prefill wave behind
        the wave's first-token samplers, before any is synced: lanes
        already decoding enter from host state, the wave's lanes
        (`firsts`: _prefill_pending's groups) where their first token
        will leave them, that token taken from the sampler's output on
        the device. The host would build the same inputs one sync
        later. True when the burst is in flight; False leaves the
        caller's sync-then-_decode_iter order, which is every case the
        speculative burst leaves too (a lane that needs the constrained
        burst or is cancelled, a burst in flight, a pool that cannot
        cover the lanes without a preemption) and the ragged round. A
        lane its first token ends is overshoot in this one burst:
        _emit_burst skips it and its pages wait in the burst's
        `deferred`. Call under the device lock."""
        cfg = self.config
        batch = list(self._running)
        fresh = {id(s) for group, _, _ in firsts for s in group}
        k = cfg.decode_steps_per_sync
        if (self._inflight is not None or not cfg.pipeline_bursts
                or self._ragged_active()
                or any(s.needs_constrained or s.ctx.is_cancelled()
                       for s in batch)
                # every lane ends with its first token: all overshoot
                or not any(s.max_tokens - s.generated > (id(s) in fresh)
                           for s in batch)
                or not all(self._cover(
                    s, (len(s.prompt) if id(s) in fresh else s.pos)
                    + k - 1) for s in batch)):
            return False
        b = cfg.max_batch_size
        with self._span("decode_prep"):
            lanes = self._decode_lane_arrays(batch, fresh)
            lane_of = {id(s): i for i, s in enumerate(batch)}
            merges = []
            for group, sampled, _ in firsts:
                take = np.zeros(b, dtype=bool)
                col = np.zeros(b, dtype=np.int32)
                for j, s in enumerate(group):
                    take[lane_of[id(s)]] = True
                    col[lane_of[id(s)]] = j
                merges.append((sampled, take, col))
        tk = self.TOPK_WIDTH if any(s.wants_topk for s in batch) else 0
        await asyncio.to_thread(
            self._launch_burst, batch, lanes, k, tk, merges)
        self.metrics.chained_refills.inc()
        return True

    def _first_token_packed(self, pending: list[_Seq], last_logits):
        """_first_token_dispatch + ONE host sync. Returns (packed np
        (2 + 2*tk, width), tk). Device-blocking — call under the device
        lock, in a thread."""
        sampled, tk = self._first_token_dispatch(pending, last_logits)
        return self._host_sync(sampled), tk

    def _first_token_dispatch(self, pending: list[_Seq], last_logits):
        """Launch the sampling of every just-prefilled sequence's FIRST
        token in one device call, max_batch_size wide (rows past the
        group repeat its first). last_logits[id(s)] = (a round's (Bp, V)
        logits, s's row in it). A group out of ONE round (every group
        of the by-sequence order) hands that array and a row vector to
        the sampler, which gathers inside the program: one launch, one
        program a prefill width. Rows out of several rounds (lockstep
        order of draft and pp engines) are sliced and stacked first.
        Grammar masks and penalties overlay the gathered rows. Returns
        (sampled on the device (2 + 2*tk, width), tk) without waiting.
        Call under the device lock, in a thread. Shared by the
        all-at-once prefill and the budgeted scheduler's completions so
        first-token semantics can never diverge."""
        cfg, mcfg = self.config, self.model_cfg
        width = cfg.max_batch_size
        with self._span("sample_first"):
            srcs = [last_logits[id(s)] for s in pending]
            srcs += [srcs[0]] * (width - len(srcs))
            logits, rows = srcs[0][0], None
            if all(a is logits for a, _ in srcs):
                rows = np.asarray([r for _, r in srcs], dtype=np.int32)
            else:
                logits = jax.numpy.stack([a[r] for a, r in srcs])
            guided_mask = None
            if any(s.guided is not None for s in pending):
                # first sampled token must already respect the grammar
                V = mcfg.vocab_size
                guided_mask = np.zeros((width, V), dtype=np.float32)
                for i, s in enumerate(pending):
                    if s.guided is not None:
                        ok = self._guided_allowed_row(s.guided, s, V)
                        guided_mask[i, ~ok] = -1e30
            penalty_args = None
            if any(s.has_penalties for s in pending):
                # the FIRST sampled token must see the same penalties as
                # every decode-burst token (vLLM semantics: repetition
                # covers prompt tokens)
                penalty_args = self._penalty_arrays(pending, width)

            def arr(fn, dtype):
                vals = [fn(s) for s in pending]
                vals += [vals[0]] * (width - len(pending))
                return np.asarray(vals, dtype=dtype)

            if rows is not None and (penalty_args is not None
                                     or guided_mask is not None):
                logits, rows = logits[rows], None
            if penalty_args is not None:
                from dynamo_tpu.engine.sampling import apply_penalties

                rep_a, freq_a, pres_a, pc, oc = penalty_args
                logits = apply_penalties(
                    logits, jax.numpy.asarray(pc),
                    jax.numpy.asarray(oc),
                    jax.numpy.asarray(rep_a), jax.numpy.asarray(freq_a),
                    jax.numpy.asarray(pres_a))
            if guided_mask is not None:
                logits = logits + jax.numpy.asarray(guided_mask)
            tk = (self.TOPK_WIDTH
                  if any(s.wants_topk for s in pending) else 0)
            lane_arrays = (
                arr(lambda s: s.seed, np.uint32),
                arr(lambda s: s.generated, np.uint32),
                arr(lambda s: s.req.sampling.temperature, np.float32),
                arr(lambda s: s.req.sampling.top_p, np.float32),
                arr(lambda s: s.req.sampling.top_k, np.int32),
                arr(lambda s: s.req.sampling.min_p, np.float32))
        # the sampler is one program a height of the logits it is handed
        trk = self.metrics.compile.track(
            "sample_first", (width, tk, logits.shape[0]))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)
        with trk:
            sampled = self._mesh_dispatch(
                trk, sample_tokens_lp, logits, *lane_arrays, rows=rows,
                topk_lp=tk, span_tokens=len(pending), routed_tokens=0,
                temps=lane_arrays[2])
        rec = self.step_recorder
        if rec is not None:
            rec.record("sample_first", trk.shape, trk.elapsed_s,
                       good_tokens=len(pending), work_tokens=width,
                       lanes=len(pending), width=width,
                       tokens=len(pending), compiled=trk.compiled,
                       synced=False)
        return sampled, tk

    def _emit_first_tokens(self, pending: list[_Seq], packed: np.ndarray,
                           tk: int, draft_done: bool) -> None:
        """Flip just-prefilled sequences to decodable and emit their
        first tokens (packed from _first_token_packed). draft_done=False
        (budgeted path): the draft cache saw none of the prompt — leave
        draft_pos at 0 so _draft_catchup replays it before the first
        spec burst (the draft is small by construction)."""
        with self._span("emit"):
            mcfg = self.model_cfg
            tokens = packed[0].astype(np.int32)
            logprobs = packed[1]
            self.metrics.prefill_emitted.inc(len(pending))
            for i, (seq, token, lp) in enumerate(zip(pending, tokens,
                                                     logprobs)):
                # token_seq mirrors what prefill wrote to the device; register
                # every complete block this worker now holds (no-op for blocks
                # matched from already-registered shared pages)
                seq.token_seq = TokenBlockSequence(mcfg.page_size, seq.prompt)
                for block in (() if self.recurrent
                              else seq.token_seq.blocks):
                    self.pool.register_page(
                        seq.pages[block.block_index], block.seq_hash,
                        block.local_hash, block.parent_seq_hash)
                seq.prefilled = True
                seq.prefill_pos = len(seq.prompt)
                seq.draft_pos = len(seq.prompt) if draft_done else 0
                topk_fn = None
                if tk and seq.wants_topk:
                    def topk_fn(_k, _i=i, _s=seq):
                        return _topk_list(
                            packed[2:2 + tk, _i],
                            packed[2 + tk:2 + 2 * tk, _i],
                            min(_s.req.sampling.top_logprobs, tk))

                self._emit_lane(seq, np.asarray([token]), [float(lp)],
                                topk_fn, append_inputs=False)

    async def _prefill_budgeted(self) -> bool:
        """Token-budgeted interleaved prefill step: advance pending
        sequences' chunk cursors by at most prefill_chunk_budget prompt
        tokens in ONE chunk round, instead of running every chunk round
        back-to-back under the device lock. Decode lanes therefore emit
        tokens BETWEEN a long prompt's chunks — ITL is bounded by one
        budgeted step, not one full prefill. Where the engine shape
        allows, the round FUSES with the decode burst in one jitted
        mixed step (mixed_prefill_decode) so the chunk rides the burst's
        weight stream; otherwise the round runs alone and _decode_iter
        interleaves between scheduler iterations. Sequences whose cursor
        reaches len(prompt) get their first token through the SAME
        sampling/emission helpers as the legacy path."""
        pending = [s for s in self._running if not s.prefilled]
        if not pending:
            return False
        mcfg, cfg = self.model_cfg, self.config
        for s in list(pending):
            if s.ctx.is_cancelled():
                # legacy prefill lets cancellation surface at decode;
                # mid-prefill cursors can idle for many iterations, so
                # reap here and free the partial pages early
                self._finish(s, FINISH_CANCELLED)
                pending.remove(s)
        if not pending:
            return True
        for s in pending:
            # KVBM/remote onboarding may advance the cached prefix after
            # admission; the cursor resumes where the cache ends
            s.prefill_pos = max(s.prefill_pos, s.cached_len)
        offsets = {id(s): s.prefill_pos for s in pending}

        needs_stage = any(s.import_kv is not None for s in pending) or (
            self._sp_params is not None
            and cfg.sp_threshold > 0
            and any(offsets[id(s)] == 0
                    and len(s.prompt) >= cfg.sp_threshold
                    for s in pending))
        if needs_stage:
            # disagg imports land before any chunk touches the pages; SP
            # bulk prefill is ONE ring dispatch covering >= half of an
            # eligible novel long prompt — it deliberately overruns the
            # token budget once (the ring kernel is the cheaper way to
            # move that many tokens; docs/scheduler.md)
            def stage():
                for seq in pending:
                    if seq.import_kv is not None:
                        data, n_tok = seq.import_kv
                        n_pages = (n_tok + mcfg.page_size - 1) \
                            // mcfg.page_size
                        self.write_kv_pages(seq.pages[:n_pages], data)
                        seq.import_kv = None
                if self._sp_params is not None:
                    self._sp_bulk_prefill(pending, offsets)

            async with self._device_lock:
                await asyncio.to_thread(stage)
            for s in pending:
                s.prefill_pos = offsets[id(s)]

        # pick chunks in arrival order up to the budget, aligned group
        # first (mirrors _chunk_round_once's grouping, so the picks ARE
        # the round's active set)
        aligned_s = [s for s in pending
                     if offsets[id(s)] % mcfg.page_size == 0]
        pool_ = aligned_s or pending
        aligned = bool(aligned_s)
        picks: list[_Seq] = []
        caps: dict[int, int] = {}
        rem = cfg.prefill_chunk_budget
        for s in pool_:
            if rem <= 0 or len(picks) >= cfg.max_batch_size:
                break
            take = min(len(s.prompt) - offsets[id(s)],
                       cfg.prefill_chunk, rem)
            if take <= 0:
                continue
            picks.append(s)
            caps[id(s)] = take
            rem -= take
        if not picks:
            return needs_stage
        picks = picks[:self._prefill_width(len(picks))]
        chunk_lens = [caps[id(s)] for s in picks]
        self.metrics.prefill_new_tokens.inc(sum(chunk_lens))

        # fuse the round with a decode burst when nothing forces a
        # special burst shape: no burst already in flight, no draft/pp
        # engine, and no decode lane needing the constrained head.
        # Fallback is NOT a stall — the round runs alone and
        # _decode_iter still interleaves between iterations.
        runnable = [s for s in self._running if s.prefilled]
        k_steps = cfg.decode_steps_per_sync
        batch: list[_Seq] = []
        if (runnable and self._inflight is None
                and self.draft_params is None and cfg.pp_mesh is None):
            with self._span("decode_prep"):
                self._prep_decode_lanes(runnable, k_steps)
            batch = runnable[:cfg.max_batch_size]
            if any(s.needs_constrained for s in batch):
                batch = []
        if batch:
            return await self._mixed_step(picks, offsets, caps, batch,
                                          k_steps, aligned)

        def round_():
            if cfg.pp_mesh is not None:
                return self._pp_chunk_round(picks, offsets, caps)
            kc, vc, done, _ = self._chunk_round_once(
                self.params, mcfg, self.k_cache, self.v_cache, picks,
                offsets, tokens_of=lambda s: s.prompt,
                target_len_of=lambda s: len(s.prompt), caps=caps)
            self.k_cache, self.v_cache = kc, vc
            return done

        async with self._device_lock:
            done_logits = await asyncio.to_thread(round_)
        for s in picks:
            s.prefill_pos = offsets[id(s)]
        await self._finish_first_tokens(picks, done_logits)
        return True

    async def _mixed_step(self, picks: list[_Seq], offsets, caps,
                          batch: list[_Seq], k_steps: int,
                          aligned: bool) -> bool:
        """Dispatch ONE jitted mixed prefill+decode step: the picks'
        chunk sub-batch and the decode burst share the device dispatch
        (and each layer's weight stream). Decode lanes' tokens emit from
        this step exactly as a plain burst's would."""
        if self._ragged_active():
            return await self._ragged_mixed(picks, offsets, caps, batch)
        cfg, mcfg = self.config, self.model_cfg
        with self._span("prefill_prep"):
            bp = self._prefill_width(len(picks))
            chunk_lens = [caps[id(s)] for s in picks]
            t_bucket = self._token_bucket(max(chunk_lens))
            ch_toks = np.zeros((bp, t_bucket), dtype=np.int32)
            ch_tables = np.zeros((bp, mcfg.max_pages_per_seq),
                                 dtype=np.int32)
            ch_cached = np.zeros(bp, dtype=np.int32)
            ch_seq_lens = np.zeros(bp, dtype=np.int32)
            for i, s in enumerate(picks):
                off, n = offsets[id(s)], chunk_lens[i]
                ch_toks[i, :n] = s.prompt[off:off + n]
                ch_tables[i, :len(s.pages)] = s.pages
                ch_cached[i] = off
                ch_seq_lens[i] = off + n

            b = cfg.max_batch_size
            (tokens, positions, page_tables, valid, seeds, steps, temps,
             top_ps, top_ks) = self._decode_lane_arrays(batch)
            tk = self.TOPK_WIDTH if any(s.wants_topk for s in batch) else 0

        trk = self.metrics.compile.track(
            "mixed_step", (bp, t_bucket, k_steps, int(aligned), tk))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)

        def dispatch():
            with trk:
                packed, ch_logits, kc, vc = self._mesh_dispatch(
                    trk, mixed_prefill_decode,
                    self.params, self.k_cache, self.v_cache,
                    jax.numpy.asarray(ch_toks),
                    jax.numpy.asarray(ch_tables),
                    jax.numpy.asarray(ch_cached),
                    jax.numpy.asarray(ch_seq_lens),
                    jax.numpy.asarray(tokens),
                    jax.numpy.asarray(positions),
                    jax.numpy.asarray(page_tables),
                    jax.numpy.asarray(valid), jax.numpy.asarray(seeds),
                    jax.numpy.asarray(steps), jax.numpy.asarray(temps),
                    jax.numpy.asarray(top_ps),
                    jax.numpy.asarray(top_ks),
                    mcfg, k_steps, aligned, tk,
                    span_tokens=sum(chunk_lens) + len(batch) * k_steps,
                    temps=temps)
                # ONE host sync; chunk logits stay on device for the
                # first-token sampler
                return self._host_sync(packed), ch_logits, kc, vc

        async with self._device_lock:
            packed, ch_logits, self.k_cache, self.v_cache = \
                await asyncio.to_thread(dispatch)
        self.metrics.prefill_chunk.observe(trk.elapsed_s)
        self.metrics.mixed_steps.inc()
        self.metrics.decode_steps_during_prefill.inc(k_steps)
        rec = self.step_recorder
        if rec is not None:
            # one dispatch doing both kinds of work: goodput = real
            # chunk tokens + real decode lane-steps; work = the padded
            # (bp x t_bucket) chunk block + the fixed-width burst
            rec.record("mixed_step", trk.shape, trk.elapsed_s,
                       good_tokens=(sum(chunk_lens)
                                    + len(batch) * k_steps),
                       work_tokens=bp * t_bucket + b * k_steps,
                       lanes=len(picks) + len(batch),
                       width=bp + b, tokens=len(batch) * k_steps,
                       compiled=trk.compiled)
        self._mark_decode_compile(batch, trk)
        self._trace_chunk(picks, chunk_lens, trk, mixed=True)
        done_logits: dict[int, Any] = {}
        for i, s in enumerate(picks):
            offsets[id(s)] += chunk_lens[i]
            s.prefill_pos = offsets[id(s)]
            if s.prefill_pos >= len(s.prompt):
                done_logits[id(s)] = (ch_logits, i)
        self._emit_burst(batch, packed, k_steps, tk)
        await self._finish_first_tokens(picks, done_logits)
        return True

    def _trace_chunk(self, picks: list[_Seq], chunk_lens: list[int],
                     trk, mixed: bool = False) -> None:
        """Per-traced-pick prefill-chunk stage span. With tracing off
        every pick's trace is None — the scan allocates nothing."""
        if all(s.trace is None for s in picks):
            return
        end_ns = time.time_ns()
        start_ns = end_ns - int(trk.elapsed_s * 1e9)
        for i, s in enumerate(picks):
            if s.trace is not None:
                s.trace.stage(
                    "engine.prefill.chunk", start_ns, end_ns,
                    tokens=chunk_lens[i], entry=trk.entry,
                    mixed=mixed, compiled=trk.compiled)

    async def _finish_first_tokens(self, picks: list[_Seq],
                                   done_logits: dict[int, Any]) -> None:
        """Sample + emit first tokens for the picks whose cursor reached
        the end of the prompt this round (budgeted path: the draft cache
        saw none of the prompt, so draft_pos stays 0 and _draft_catchup
        replays it before the first spec burst)."""
        completed = [s for s in picks if id(s) in done_logits]
        if not completed:
            return
        async with self._device_lock:
            packed, tk = await asyncio.to_thread(
                self._first_token_packed, completed, done_logits)
        self._emit_first_tokens(completed, packed, tk, draft_done=False)

    def _pp_chunk_round(self, picks: list[_Seq], offsets,
                        caps) -> dict[int, Any]:
        """Budgeted chunk round on a pipeline-parallel engine: one
        pp_prefill_paged call over the picks' capped chunks (the pp
        analog of _chunk_round_once; cached = the cursor). Returns
        {id(s): last-token logits} for completions."""
        from dynamo_tpu.models.llama_pp import pp_prefill_paged

        cfg, mcfg = self.config, self.model_cfg
        n_stages = cfg.pp_mesh.shape["pp"]
        chunk = min(cfg.prefill_chunk, 128)
        with self._span("prefill_prep"):
            takes = [caps[id(s)] for s in picks]
            t_pad = _next_pow2(max(max(takes), chunk * n_stages), chunk,
                               1 << 30)
            b_pad = _next_pow2(len(picks), 1, cfg.max_batch_size)
            tokens = np.zeros((b_pad, t_pad), dtype=np.int32)
            tables = np.zeros((b_pad, mcfg.max_pages_per_seq),
                              dtype=np.int32)
            cached = np.zeros(b_pad, dtype=np.int32)
            seq_lens = np.zeros(b_pad, dtype=np.int32)
            for i, s in enumerate(picks):
                off, n = offsets[id(s)], takes[i]
                tokens[i, :n] = s.prompt[off:off + n]
                tables[i, :len(s.pages)] = s.pages
                cached[i] = off
                seq_lens[i] = off + n
        trk = self.metrics.compile.track("pp_prefill", (b_pad, t_pad))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)
        with trk:
            logits, self.k_cache, self.v_cache = self._mesh_dispatch(
                trk, pp_prefill_paged,
                self.params, self.k_cache, self.v_cache,
                jax.numpy.asarray(tokens), jax.numpy.asarray(tables),
                cached, seq_lens, mcfg, cfg.pp_mesh, chunk,
                span_tokens=sum(takes))
        self.metrics.prefill_chunk.observe(trk.elapsed_s)
        rec = self.step_recorder
        if rec is not None:
            rec.record("pp_prefill", trk.shape, trk.elapsed_s,
                       good_tokens=sum(takes),
                       work_tokens=b_pad * t_pad, lanes=len(picks),
                       width=b_pad, compiled=trk.compiled,
                       synced=False)
        self._trace_chunk(picks, takes, trk)
        done: dict[int, Any] = {}
        for i, s in enumerate(picks):
            offsets[id(s)] += takes[i]
            if offsets[id(s)] >= len(s.prompt):
                done[id(s)] = (logits, i)
        return done

    # -- decode -------------------------------------------------------------

    def _prep_decode_lanes(self, runnable: list[_Seq],
                           k_steps: int) -> None:
        """Ready `runnable` (mutated in place) for a k_steps decode
        burst: drop cancelled lanes, and grow every lane's page list to
        cover pos .. pos+k_steps-1 — preempting victims when the pool
        runs dry. Shared by _decode_iter and the budgeted scheduler's
        mixed dispatch so preemption semantics can't diverge."""
        mcfg = self.model_cfg
        # every runnable seq needs pages covering pos .. pos+k_steps-1
        for s in list(runnable):
            if s not in runnable:
                # preempted as an earlier seq's victim in this same pass:
                # it is back in _waiting with no pages — allocating into it
                # here would leak pages when _admit re-allocates
                continue
            if s.ctx.is_cancelled():
                self._finish(s, FINISH_CANCELLED)
                runnable.remove(s)
                continue
            need = (s.pos + k_steps - 1) // mcfg.page_size + 1
            while len(s.pages) < need:
                pid = self.pool.allocate_page()
                if pid is None:
                    victim = self._pick_victim(exclude=s)
                    if victim is not None and victim in runnable:
                        runnable.remove(victim)
                    pid = self.pool.allocate_page()
                if pid is None:
                    self._preempt(s)
                    runnable.remove(s)
                    break
                s.pages.append(pid)

    def _cover(self, seq: _Seq, last_pos: int) -> bool:
        """Grow seq.pages to hold position `last_pos` without preempting
        anyone. False when the page table or the pool cannot; pages
        taken so far stay attached (no leak: the lane's next burst
        wants them anyway)."""
        mcfg = self.model_cfg
        need = last_pos // mcfg.page_size + 1
        if need > mcfg.max_pages_per_seq:
            return False
        while len(seq.pages) < need:
            pid = self.pool.allocate_page()
            if pid is None:
                return False
            seq.pages.append(pid)
        return True

    def _slot_kw(self, seqs: list[_Seq], width: int) -> dict:
        """`slots=` for an entry of a model with recurrent layers: each
        sequence's state slot, `width` wide, rows past `seqs` at scratch
        slot 0. Nothing for every other model, whose entries take none."""
        if not self.recurrent:
            return {}
        slots = np.zeros(width, dtype=np.int32)
        slots[:len(seqs)] = [s.slot for s in seqs]
        return {"slots": jax.numpy.asarray(slots)}

    def _give_slot(self, seq: _Seq) -> None:
        """Return a sequence's state slot, wherever its pages go. No burst
        in flight has to be waited for: the slot's next tenant starts from
        zero in a first chunk launched behind it."""
        if seq.slot:
            self.slots.give(seq.slot)
            seq.slot = 0
            self.metrics.state_slots_in_use.set(self.slots.in_use)

    def _decode_lane_arrays(self, batch: list[_Seq], fresh=()) -> tuple:
        """The nine per-lane inputs of a decode burst, max_batch_size
        wide, in the order every decode entry takes them: tokens,
        positions, page_tables, valid, seeds, steps, temps, top_ps,
        top_ks. A lane whose id is in `fresh` has been prefilled on the
        device but its first token is not on the host yet: it enters
        where _emit_first_tokens will leave it (position len(prompt),
        one step on) and its token is left 0 for the device to fill."""
        b = self.config.max_batch_size
        tokens = np.zeros(b, dtype=np.int32)
        positions = np.zeros(b, dtype=np.int32)
        page_tables = np.zeros((b, self.model_cfg.max_pages_per_seq),
                               dtype=np.int32)
        valid = np.zeros(b, dtype=bool)
        seeds = np.zeros(b, dtype=np.uint32)
        steps = np.zeros(b, dtype=np.uint32)
        temps = np.zeros(b, dtype=np.float32)
        top_ps = np.ones(b, dtype=np.float32)
        top_ks = np.zeros(b, dtype=np.int32)
        for i, s in enumerate(batch):
            new = id(s) in fresh
            tokens[i] = 0 if new else s.next_token
            positions[i] = len(s.prompt) if new else s.pos
            page_tables[i, :len(s.pages)] = s.pages
            valid[i] = True
            seeds[i] = s.seed
            steps[i] = s.generated + new
            temps[i] = s.req.sampling.temperature
            top_ps[i] = s.req.sampling.top_p
            top_ks[i] = s.req.sampling.top_k
        return (tokens, positions, page_tables, valid, seeds, steps,
                temps, top_ps, top_ks)

    def _launch_burst(self, batch: list[_Seq], lanes: tuple,
                      k_steps: int, tk: int, firsts=()) -> None:
        """Dispatch the plain fused burst over `lanes`
        (_decode_lane_arrays) WITHOUT syncing and make it the burst in
        flight. `firsts` = (sampled, take, col) a first-token sampler
        not yet synced: the lanes in `take` get their input token on
        the device, from column `col` of its packed output. Call under
        the device lock, in a thread (a first call compiles)."""
        cfg, mcfg = self.config, self.model_cfg
        b = cfg.max_batch_size
        (tokens, positions, page_tables, valid, seeds, steps, temps,
         top_ps, top_ks) = lanes
        trk = self.metrics.compile.track("decode_burst", (b, k_steps, tk))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)
        tokens_dev = jax.numpy.asarray(tokens)
        if cfg.mesh is not None:
            # placed as the tokens made on the device arrive (a
            # sampler's or a burst's output, replicated over the mesh):
            # the entry is lowered per input placement, and a burst
            # built from the host must not meet a program of its own
            from jax.sharding import NamedSharding, PartitionSpec

            tokens_dev = jax.device_put(
                tokens_dev, NamedSharding(cfg.mesh, PartitionSpec()))
        for sampled, take, col in firsts:
            tokens_dev = _first_tokens_into(tokens_dev, sampled, take, col)
        with trk:
            packed, self.k_cache, self.v_cache = self._mesh_dispatch(
                trk, self._decode_multi_step,
                self.params, self.k_cache, self.v_cache, tokens_dev,
                jax.numpy.asarray(positions),
                jax.numpy.asarray(page_tables),
                jax.numpy.asarray(valid), jax.numpy.asarray(seeds),
                jax.numpy.asarray(steps), jax.numpy.asarray(temps),
                jax.numpy.asarray(top_ps), jax.numpy.asarray(top_ks),
                mcfg, k_steps, topk_lp=tk, temps=temps,
                span_tokens=len(batch) * k_steps, **self._slot_kw(batch, b))
        rec = self.step_recorder
        if rec is not None:
            # pipelined: the dispatch returns without a host sync,
            # so this is dispatch-only time (synced=False); the
            # honest device wait records as `burst_sync` when
            # _pipeline_consume pulls the results
            rec.record("decode_burst", trk.shape, trk.elapsed_s,
                       good_tokens=len(batch) * k_steps,
                       work_tokens=b * k_steps, lanes=len(batch),
                       width=b, tokens=len(batch) * k_steps,
                       compiled=trk.compiled, synced=False)
        self._mark_decode_compile(batch, trk)
        self._inflight = {
            "k": k_steps, "batch": batch, "packed": packed,
            "positions": positions, "valid": valid, "seeds": seeds,
            "steps": steps, "temps": temps, "top_ps": top_ps,
            "top_ks": top_ks, "tk": tk, "deferred": [], "firsts": [],
            # when the device is taken to have started it: now, or once
            # the samplers ahead of a chained one are done
            "t0": time.perf_counter()}

    async def _decode_iter(self) -> bool:
        if self._dllm:
            return await self._block_decode()
        if self._inflight is not None:
            return await self._pipeline_consume()
        runnable = [s for s in self._running if s.prefilled]
        if not runnable:
            return False
        mcfg, cfg = self.model_cfg, self.config
        # Fixed burst length + fixed batch width below ⇒ exactly ONE decode
        # compilation for the engine's lifetime. Underfull lanes/steps waste
        # a little compute; recompiles (tens of seconds) waste far more.
        # Spec bursts serve EVERY sampling config (the rejection test
        # runs on each lane's FILTERED, penalty-adjusted, DFA-masked
        # distribution — engine/spec.py), so a draft engine always
        # speculates; only non-spec engines route constrained lanes to
        # the constrained burst.
        # spec_shrink is the brownout stage-3 actuator: fall back to the
        # already-compiled non-spec burst (no new XLA shapes), freeing
        # draft-model compute and HBM bandwidth for interactive TTFT.
        use_spec = self.draft_params is not None and not self.spec_shrink
        k_steps = (cfg.spec_iters_per_sync * (cfg.spec_gamma + 1)
                   if use_spec else cfg.decode_steps_per_sync)
        with self._span("decode_prep"):
            self._prep_decode_lanes(runnable, k_steps)
        if not runnable:
            return False
        b = cfg.max_batch_size
        batch = runnable[:b]
        # Ensure every guided lane's grammar is registered BEFORE any
        # lane arrays are sized or the device stack is fetched: the
        # _guided_slot_of backstop can evict+renumber other slots, so
        # registration must fully settle first. A lane whose grammar
        # can't be re-admitted (table byte cap) fails alone, never the
        # batch.
        for s in [x for x in batch if x.guided is not None]:
            try:
                self._guided_slot_of(s)
            except ValueError as e:
                s.queue.put_nowait(EngineOutput(
                    token_ids=[], finish_reason=FINISH_ERROR,
                    extra={"error": f"guided decoding: {e}"}).to_dict())
                self._finish(s, FINISH_ERROR, emit=False)
                batch.remove(s)
        if not batch:
            return True          # progressed: lanes finished with errors
        # top-k alternatives ride the packed burst only when some lane
        # asked (separate compiled variant; hot path unaffected)
        tk = self.TOPK_WIDTH if any(s.wants_topk for s in batch) else 0
        if (self._ragged_active()
                and not any(s.needs_constrained for s in batch)):
            # flat one-row-per-lane round; constrained lanes keep the
            # guided burst (grammar masks/penalties live in that entry)
            return await self._ragged_decode(batch, tk)
        if any(not s.prefilled for s in self._running):
            # decode progressed while some prompt's prefill is still
            # mid-flight — the interleaving the budgeted scheduler
            # exists to create (every path below dispatches a burst)
            self.metrics.decode_steps_during_prefill.inc(k_steps)
        with self._span("decode_prep"):
            lanes = self._decode_lane_arrays(batch)
        (tokens, positions, page_tables, valid, seeds, steps, temps,
         top_ps, top_ks) = lanes

        if use_spec:
            from dynamo_tpu.engine.spec import spec_decode_multi_step

            stale = [s for s in batch if s.draft_pos < s.pos]
            if stale:
                # tokens decoded via non-spec fallback bursts never wrote
                # draft KV; replay them through the draft before the spec
                # burst or its proposals attend garbage
                await self._draft_catchup(stale)

            use_guided = any(s.guided is not None for s in batch)
            gkw = {}
            if use_guided:
                g_ids, g_states, stop_ids_a = \
                    self._guided_lane_arrays(batch, b)
                g_bits, g_next, g_eos_ok = self._guided_device_stack()
                gkw = dict(use_guided=True, g_bits=g_bits, g_next=g_next,
                           g_eos_ok=g_eos_ok,
                           g_ids=jax.numpy.asarray(g_ids),
                           g_states=jax.numpy.asarray(g_states),
                           stop_ids=jax.numpy.asarray(stop_ids_a))
            if any(s.req.sampling.min_p > 0.0 for s in batch):
                min_ps = np.zeros(b, dtype=np.float32)
                for i, s in enumerate(batch):
                    min_ps[i] = s.req.sampling.min_p
                gkw["min_p"] = jax.numpy.asarray(min_ps)
            if any(s.has_penalties for s in batch):
                rep_p, freq_p, pres_p, p_cnt, o_cnt = \
                    self._penalty_arrays(batch, b)
                gkw.update(
                    use_penalties=True,
                    rep_pen=jax.numpy.asarray(rep_p),
                    freq_pen=jax.numpy.asarray(freq_p),
                    pres_pen=jax.numpy.asarray(pres_p),
                    prompt_counts=jax.numpy.asarray(p_cnt),
                    out_counts=jax.numpy.asarray(o_cnt))

            trk = self.metrics.compile.track(
                "spec_decode",
                (b, cfg.spec_gamma, cfg.spec_iters_per_sync, tk,
                 *sorted(gkw)))
            led = self.memory_ledger
            if led is not None:
                led.on_dispatch(trk.entry, trk.shape,
                                compiled=trk.compiled)

            def run_spec_burst():
                packed, kc, vc, dk, dv, _ = self._mesh_dispatch(
                    trk, spec_decode_multi_step,
                    self.params, self.draft_params,
                    self.k_cache, self.v_cache, self.dk_cache,
                    self.dv_cache, jax.numpy.asarray(tokens),
                    jax.numpy.asarray(positions),
                    jax.numpy.asarray(page_tables),
                    jax.numpy.asarray(valid), jax.numpy.asarray(seeds),
                    jax.numpy.asarray(steps), jax.numpy.asarray(temps),
                    jax.numpy.asarray(top_ps), jax.numpy.asarray(top_ks),
                    mcfg, cfg.draft_model, cfg.spec_gamma,
                    cfg.spec_iters_per_sync, topk_lp=tk,
                    span_tokens=len(batch) * k_steps, **gkw)
                # ONE host sync
                return self._host_sync(packed), kc, vc, dk, dv

            async with self._device_lock:
                with trk:
                    (packed, self.k_cache, self.v_cache, self.dk_cache,
                     self.dv_cache) = \
                        await asyncio.to_thread(run_spec_burst)
            rec = self.step_recorder
            if rec is not None:
                # good = real lanes' draft+verify positions; rejected
                # proposals still count as computed work, acceptance is
                # tracked separately in SpecDecodeStats
                rec.record("spec_decode", trk.shape, trk.elapsed_s,
                           good_tokens=len(batch) * k_steps,
                           work_tokens=b * k_steps, lanes=len(batch),
                           width=b, compiled=trk.compiled)
            self._mark_decode_compile(batch, trk)
            toks_out = packed[0].astype(np.int32)   # (S, gamma+1, B)
            lps_out = packed[1]                     # (S, gamma+1, B)
            counts = packed[2, :, 0, :].astype(np.int32)  # (S, B)
            stk_ids = stk_lps = None
            if tk:
                stk_ids = packed[3:3 + tk].astype(np.int32)
                stk_lps = packed[3 + tk:3 + 2 * tk]
            st = self._spec_stats
            G1 = cfg.spec_gamma + 1
            slot_grid = np.arange(G1)[None, :]       # (1, G1)
            for i, s in enumerate(batch):
                if s.finished or s not in self._running:
                    continue
                cnts = counts[:, i]                  # (S,)
                emit_mask = slot_grid < cnts[:, None]    # (S, G1)
                flat_toks = toks_out[:, :, i][emit_mask]  # iter-major
                flat_lps = lps_out[:, :, i][emit_mask]
                topk_fn = None
                if tk and s.wants_topk:
                    # flat index -> (iter, slot) for the packed topk rows
                    its, slots = np.nonzero(emit_mask)
                    w = min(s.req.sampling.top_logprobs, tk)

                    def topk_fn(k, _i=i, _w=w, _its=its, _slots=slots):
                        return _topk_list(
                            stk_ids[:, _its[k], _slots[k], _i],
                            stk_lps[:, _its[k], _slots[k], _i], _w)

                n_emitted = self._emit_lane(s, flat_toks, flat_lps,
                                            topk_fn)
                # acceptance stats over the CONSUMED iterations (the
                # iteration that finishes the lane counts, later ones
                # are overshoot — same accounting as per-token emission)
                consumed = 0 if n_emitted == 0 else min(
                    int(np.searchsorted(np.cumsum(cnts), n_emitted,
                                        side="left")) + 1,
                    cfg.spec_iters_per_sync)
                st.num_draft_tokens += cfg.spec_gamma * consumed
                st.num_accepted_tokens += int(
                    (cnts[:consumed] - 1).sum())
                s.draft_pos = s.pos
            return True

        use_constrained = any(s.needs_constrained for s in batch)
        if use_constrained:
            from dynamo_tpu.models.llama import decode_multi_step_guided

            # slots are stable here: every batch grammar was registered
            # (and any backstop renumbering settled) at the top of
            # _decode_iter, before any lane arrays were built
            g_ids, g_states, stop_ids = self._guided_lane_arrays(batch, b)
            g_bits, g_next, g_eos_ok = self._guided_device_stack()
            rep_pens, freq_pens, pres_pens, prompt_counts, out_counts = \
                self._penalty_arrays(batch, b)
            min_ps = np.zeros(b, dtype=np.float32)
            for i, s in enumerate(batch):
                min_ps[i] = s.req.sampling.min_p

        if cfg.pp_mesh is not None:
            from dynamo_tpu.models.llama_pp import pp_decode_multi_step

            ckw = {}
            if use_constrained:
                # full sampling matrix on pp engines (reference serves
                # sampling uniformly regardless of parallelism:
                # trtllm_utils.py:167-176) — the SAME lane packings the
                # plain constrained burst built above
                ckw = dict(
                    use_constrained=True,
                    min_p=jax.numpy.asarray(min_ps),
                    rep_pen=jax.numpy.asarray(rep_pens),
                    freq_pen=jax.numpy.asarray(freq_pens),
                    pres_pen=jax.numpy.asarray(pres_pens),
                    prompt_counts=jax.numpy.asarray(prompt_counts),
                    out_counts=jax.numpy.asarray(out_counts),
                    g_bits=g_bits, g_next=g_next, g_eos_ok=g_eos_ok,
                    g_ids=jax.numpy.asarray(g_ids),
                    g_states=jax.numpy.asarray(g_states),
                    stop_ids=jax.numpy.asarray(stop_ids))

            def run_pp_burst():
                packed, kc, vc = self._mesh_dispatch(
                    trk, pp_decode_multi_step,
                    self.params, self.k_cache, self.v_cache,
                    jax.numpy.asarray(tokens),
                    jax.numpy.asarray(positions),
                    jax.numpy.asarray(page_tables),
                    jax.numpy.asarray(valid), jax.numpy.asarray(seeds),
                    jax.numpy.asarray(steps), jax.numpy.asarray(temps),
                    jax.numpy.asarray(top_ps), jax.numpy.asarray(top_ks),
                    mcfg, cfg.pp_mesh, k_steps,
                    n_micro=cfg.pp_microbatches, topk_lp=tk, temps=temps,
                    span_tokens=len(batch) * k_steps, **ckw)
                return self._host_sync(packed), kc, vc  # ONE host sync

            trk = self.metrics.compile.track(
                "pp_decode", (b, k_steps, tk, bool(ckw)))
            led = self.memory_ledger
            if led is not None:
                led.on_dispatch(trk.entry, trk.shape,
                                compiled=trk.compiled)
            async with self._device_lock:
                with trk:
                    packed, self.k_cache, self.v_cache = \
                        await asyncio.to_thread(run_pp_burst)
            rec = self.step_recorder
            if rec is not None:
                rec.record("pp_decode", trk.shape, trk.elapsed_s,
                           good_tokens=len(batch) * k_steps,
                           work_tokens=b * k_steps, lanes=len(batch),
                           width=b, tokens=len(batch) * k_steps,
                           compiled=trk.compiled)
            self._mark_decode_compile(batch, trk)
            self._emit_burst(batch, packed, k_steps, tk)
            return True

        if cfg.pipeline_bursts and not use_constrained:
            # plain fused burst, double-buffered: dispatch WITHOUT
            # syncing, then consume (which may speculate the next burst
            # before pulling this one's results). Dispatch runs in a
            # thread: a first-call XLA trace/compile would otherwise
            # freeze the event loop for seconds.
            async with self._device_lock:
                await asyncio.to_thread(
                    self._launch_burst, batch, lanes, k_steps, tk)
            return await self._pipeline_consume()

        def run_burst():
            if use_constrained:
                sampled, kc, vc = self._mesh_dispatch(
                    trk, decode_multi_step_guided,
                    self.params, self.k_cache, self.v_cache,
                    jax.numpy.asarray(tokens),
                    jax.numpy.asarray(positions),
                    jax.numpy.asarray(page_tables),
                    jax.numpy.asarray(valid), jax.numpy.asarray(seeds),
                    jax.numpy.asarray(steps), jax.numpy.asarray(temps),
                    jax.numpy.asarray(top_ps), jax.numpy.asarray(top_ks),
                    jax.numpy.asarray(min_ps),
                    jax.numpy.asarray(rep_pens),
                    jax.numpy.asarray(freq_pens),
                    jax.numpy.asarray(pres_pens),
                    jax.numpy.asarray(prompt_counts),
                    jax.numpy.asarray(out_counts),
                    g_bits, g_next, g_eos_ok, jax.numpy.asarray(g_ids),
                    jax.numpy.asarray(g_states),
                    jax.numpy.asarray(stop_ids), mcfg, k_steps,
                    topk_lp=tk, span_tokens=len(batch) * k_steps,
                    temps=temps)
                return self._host_sync(sampled), kc, vc
            sampled, kc, vc = self._mesh_dispatch(
                trk, self._decode_multi_step,
                self.params, self.k_cache, self.v_cache,
                jax.numpy.asarray(tokens), jax.numpy.asarray(positions),
                jax.numpy.asarray(page_tables), jax.numpy.asarray(valid),
                jax.numpy.asarray(seeds), jax.numpy.asarray(steps),
                jax.numpy.asarray(temps), jax.numpy.asarray(top_ps),
                jax.numpy.asarray(top_ks), mcfg, k_steps, topk_lp=tk,
                span_tokens=len(batch) * k_steps, temps=temps,
                **self._slot_kw(batch, b))
            return self._host_sync(sampled), kc, vc       # ONE host sync

        trk = self.metrics.compile.track(
            "decode_guided" if use_constrained else "decode_burst",
            (b, k_steps, tk))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)
        async with self._device_lock:
            with trk:
                packed, self.k_cache, self.v_cache = \
                    await asyncio.to_thread(run_burst)
        rec = self.step_recorder
        if rec is not None:
            rec.record(trk.entry, trk.shape, trk.elapsed_s,
                       good_tokens=len(batch) * k_steps,
                       work_tokens=b * k_steps, lanes=len(batch),
                       width=b, tokens=len(batch) * k_steps,
                       compiled=trk.compiled)
        self._mark_decode_compile(batch, trk)
        self._emit_burst(batch, packed, k_steps, tk)
        return True

    # -- block diffusion ---------------------------------------------------

    async def _prefill_blocks(self) -> bool:
        """Prefill of a block-diffusion engine: every admitted prompt's
        WHOLE blocks, block-causally, in the batched chunk rounds every
        engine uses. The tail past the last whole block (len % B ids) is
        the known head of the lane's first block and is committed with it.
        Prefill yields no token, so nothing is sampled and nothing is
        synced: the block burst that follows is launched behind the
        rounds."""
        pending = [s for s in self._running if not s.prefilled]
        if not pending:
            return False
        mcfg = self.model_cfg
        blk = mcfg.attn_block

        def whole(s: _Seq) -> int:
            return len(s.prompt) - len(s.prompt) % blk

        def rounds():
            offsets = {id(s): min(s.cached_len, whole(s)) for s in pending}
            self.k_cache, self.v_cache, _ = self._chunk_rounds(
                self.params, mcfg, self.k_cache, self.v_cache, pending,
                offsets, tokens_of=lambda s: s.prompt,
                target_len_of=whole)

        self.metrics.prefill_new_tokens.inc(sum(
            max(whole(s) - s.cached_len, 0) for s in pending))
        async with self._device_lock:
            await asyncio.to_thread(rounds)
        for seq in pending:
            seq.token_seq = TokenBlockSequence(
                mcfg.page_size, seq.prompt[:whole(seq)])
            for block in seq.token_seq.blocks:
                self.pool.register_page(
                    seq.pages[block.block_index], block.seq_hash,
                    block.local_hash, block.parent_seq_hash)
            seq.given = list(seq.prompt[whole(seq):])
            seq.prefilled = True
            seq.prefill_pos = len(seq.prompt)
        return True

    async def _block_decode(self) -> bool:
        """The decode burst of a block-diffusion engine: every runnable
        lane advances decode_steps_per_sync / B blocks in one dispatch
        (models/llama.py block_decode_multi_step), one host sync, then
        each lane's tokens go out in one frame. A step stays one token a
        lane, so a burst is decode_steps_per_sync tokens a lane at
        (denoising steps + 1) / B forwards a token. The burst is
        launched, then waited for with the scheduler awake: whoever
        arrives meanwhile is admitted and prefilled behind it
        (_land_burst)."""
        from dynamo_tpu.models.llama import block_decode_multi_step

        runnable = [s for s in self._running if s.prefilled]
        if not runnable:
            return False
        cfg, mcfg = self.config, self.model_cfg
        blk, steps = mcfg.attn_block, cfg.dllm_denoising_steps
        k_steps = cfg.decode_steps_per_sync
        n_blocks = k_steps // blk
        with self._span("decode_prep"):
            self._prep_decode_lanes(runnable, k_steps)
        if not runnable:
            return False
        b = cfg.max_batch_size
        batch = runnable[:b]
        with self._span("decode_prep"):
            # a lane enters at its first uncommitted position; no sampled
            # token and no step count go in (a draw is seeded by position)
            (_, positions, page_tables, valid, seeds, _, temps, top_ps,
             top_ks) = self._decode_lane_arrays(batch)
            given = np.zeros((b, blk), dtype=np.int32)
            n_given = np.zeros(b, dtype=np.int32)
            for i, s in enumerate(batch):
                given[i, :len(s.given)] = s.given
                n_given[i] = len(s.given)
        trk = self.metrics.compile.track(
            "decode_burst", (b, k_steps, blk, steps))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)
        forwards = len(batch) * n_blocks * (steps + 1)

        def launch():
            with trk:
                return self._mesh_dispatch(
                    trk, block_decode_multi_step,
                    self.params, self.k_cache, self.v_cache,
                    jax.numpy.asarray(given), jax.numpy.asarray(n_given),
                    jax.numpy.asarray(positions),
                    jax.numpy.asarray(page_tables),
                    jax.numpy.asarray(valid), jax.numpy.asarray(seeds),
                    jax.numpy.asarray(temps), jax.numpy.asarray(top_ps),
                    jax.numpy.asarray(top_ks),
                    mcfg, n_blocks, steps, cfg.dllm_unmasking_strategy,
                    span_tokens=len(batch) * k_steps,
                    routed_tokens=forwards * blk, temps=temps)

        t_launch = time.perf_counter()
        async with self._device_lock:
            # A first call compiles for seconds and goes to a thread.
            # Any other is launched from the loop's own thread: the emit
            # has just handed the loop a frame a lane to send, and a
            # launch in a thread of its own would share the GIL with
            # that work, each of its input transfers waiting out a
            # switch interval while the device waits for the burst
            packed, self.k_cache, self.v_cache = (
                await asyncio.to_thread(launch) if trk.compiled
                else launch())
        async def refill() -> int:
            behind = sum(not s.prefilled for s in self._running)
            return behind if await self._prefill_blocks() else 0

        packed = await self._land_burst(packed, refill)    # ONE host sync
        rec = self.step_recorder
        if rec is not None:
            rec.record(trk.entry, trk.shape,
                       time.perf_counter() - t_launch,
                       good_tokens=len(batch) * k_steps,
                       work_tokens=b * k_steps, lanes=len(batch),
                       width=b, tokens=len(batch) * k_steps,
                       compiled=trk.compiled)
        self.metrics.block_forwards.inc(
            len(batch) * n_blocks * steps, kind="denoise")
        self.metrics.block_forwards.inc(len(batch) * n_blocks,
                                        kind="commit")
        self.metrics.blocks.inc(len(batch) * n_blocks)
        self._mark_decode_compile(batch, trk)
        with self._span("emit"):
            ids = packed[0].astype(np.int32)         # (k_steps, B)
            for i, s in enumerate(batch):
                if s.finished or s not in self._running:
                    continue
                # the burst's blocks are committed on the device, known
                # head and all: token_seq follows, and a page whose last
                # block it completes is registered
                for t in ids[:, i]:
                    block = s.token_seq.append(int(t))
                    if block is not None:
                        self.pool.register_page(
                            s.pages[block.block_index], block.seq_hash,
                            block.local_hash, block.parent_seq_hash)
                head, s.given = len(s.given), []
                # past max_tokens or a stop token the rest of the burst
                # is overshoot, discarded here as after any burst
                self._emit_lane(s, ids[head:, i], packed[1, head:, i],
                                append_inputs=False)
        return True

    # the share of a burst's device time after which a held successor
    # is launched all the same: late enough for a caller ~60 ms away,
    # early enough that the launch (a few ms) beats the landing
    _SPEC_HOLD = 0.75

    async def _land_burst(self, packed, refill, successor=None,
                          deadline: float = 0.0) -> np.ndarray:
        """Wait for a burst in flight with the scheduler awake: the sync
        runs in a thread, and whoever arrives meanwhile and finds a lane
        and pages is admitted at once (_admit, its rules unchanged).
        `refill()` launches the prefill of everyone admitted and not yet
        prefilled, as ONE batch as the loop's own prefill would make it
        (_prefill_blocks for a block burst, _prefill_behind for a dense
        one), and returns how many sequences that was. It is called
        behind the burst as soon as every lane is taken, since nobody
        else can join the batch then, and otherwise when the burst
        lands, ahead of its emission, so the device goes from the burst
        (a dense one's rounds queue behind its speculative successor
        where it has one) to the rounds while the host emits and builds
        the next burst. A round writes only the new sequences' pages and
        slots, and none of them can belong to a lane of the burst: the
        pages of a lane that ended are out of the pool until the burst
        that may still write them has landed, and nothing here preempts.
        An arrival _admit turns away (no lane, no pages), or one that
        may need a remote onboard awaited, waits for the landing and the
        loop's own admit, as every arrival did. `successor()`, where a
        dense burst's speculative successor was held for a caller on its
        way back, is called once `deadline` (perf_counter) has passed."""
        async def launch() -> None:
            self.metrics.refills_behind_burst.inc(await refill())

        sync = asyncio.ensure_future(
            asyncio.to_thread(self._host_sync, packed))
        sync.add_done_callback(lambda _: self._wake.set())
        local = self.kvbm is None or self.kvbm.remote is None
        while not sync.done():
            self._wake.clear()
            if self._waiting and local:
                with self._span("admit"):
                    self._admit()
                if len(self._running) >= self.config.max_batch_size:
                    await launch()
            timeout = None
            if successor is not None:
                timeout = deadline - time.perf_counter()
                if timeout <= 0:
                    await successor()
                    successor = timeout = None
            try:
                await asyncio.wait_for(self._wake.wait(), timeout)
            except asyncio.TimeoutError:
                pass
        packed = sync.result()
        await launch()
        return packed

    def _mesh_dispatch(self, trk, fn, *args, span_tokens: int = 0,
                       routed_tokens: Optional[int] = None,
                       temps: Optional[np.ndarray] = None, **kwargs):
        """The one place every jitted dispatch passes through, on the
        thread that runs it. Armed (DYN_STEP_PROFILE) the call sits under
        a `dispatch` host span labelled as CompileTracker labels it, with
        `span_tokens` = the round's real token positions (they also count
        as rows through an MoE model's routed dispatch, unless the site
        says how many did: `routed_tokens`, 0 for a sampler); the sites
        convert their inputs (`jnp.asarray`) before they get here, so
        those transfers are outside the span. An entry that samples through
        `sample_with_logprob` (not the speculative burst, whose ratio
        test builds both sides' candidate sets for every batch) hands the
        host's copy of its lanes' temperatures (`temps`): where none
        draws the program's sampler takes its greedy branch, and the
        dispatch is counted. Mesh-recorder shim too. Off
        (mesh_recorder is None, the default): one attribute check, then
        the call — tokens and scheduler_stats stay byte-identical
        (pinned by tests/test_mesh_recorder.py). Armed: a
        freshly-compiled (entry, shape) is analyzed FIRST — lowering
        from ShapeDtypeStructs, so the donated cache buffers the real
        call consumes are never touched — then the dispatch runs and
        its cached collective bytes fold into the per-entry comm
        budget."""
        if self._routed_per_token:
            self.metrics.moe_routed_rows.inc(self._routed_per_token * (
                span_tokens if routed_tokens is None else routed_tokens))
        if temps is not None and not (temps > 0).any():
            self.metrics.sampler_greedy_dispatches.inc(entry=trk.entry)
        srec = self.step_recorder
        # ONE frame and one call site armed or not: the persistent
        # compile cache's key follows the source lines of the call stack
        # (PERF.md), and a traced run should find the programs an
        # untraced one compiled
        span = _NO_SPAN if srec is None else srec.span(
            "dispatch", entry=trk.entry,
            shape="x".join(str(x) for x in trk.shape),
            tokens=int(span_tokens))
        with self._mesh_ctx(), span:
            rec = self.mesh_recorder
            if rec is None:
                return fn(*args, **kwargs)
            if trk.compiled:
                rec.observe_compile(trk.entry, trk.shape, fn, args, kwargs,
                                    mesh=self._mesh_for_entry(trk.entry))
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            rec.record_dispatch(trk.entry, trk.shape,
                                time.perf_counter() - t0)
            return out

    def _span(self, phase: str):
        """A host span of the scheduler when the step recorder is armed,
        else the shared no-op. Never hold one across an `await`."""
        rec = self.step_recorder
        return _NO_SPAN if rec is None else rec.span(phase)

    def _host_sync(self, packed) -> np.ndarray:
        """The np.asarray round trip that ends a dispatch: the honest
        device wait (`block_until_ready` lies for pallas outputs inside
        fori_loops). Runs on the dispatch closure's thread."""
        with self._span("sync"):
            return np.asarray(packed)

    def _mesh_ctx(self):
        """Every jitted step of a mesh engine is called (hence traced)
        under its serving mesh: the Mosaic kernels split per "tp" shard
        off the ambient mesh (kernels.per_tp_shard)."""
        mesh = self.config.mesh
        return (jax.set_mesh(mesh) if mesh is not None
                else contextlib.nullcontext())

    def _mesh_for_entry(self, entry: str):
        """Mesh whose axis groups attribute this entry's collectives:
        pp entries dispatch over the pipeline mesh, everything else
        over the serving mesh (None on single-device engines — bytes
        still account, axes read '?')."""
        if entry.startswith("pp_"):
            return self.config.pp_mesh
        return self.config.mesh

    def _mark_decode_compile(self, batch: list[_Seq], trk) -> None:
        """Flag this burst's lanes when the dispatch paid an XLA compile
        — their `engine.decode` span (and any traced lane's compile
        event) gets `compiled=true` so the ITL outlier is attributable."""
        if not trk.compiled:
            return
        for s in batch:
            s.decode_compiled = True
            if s.trace is not None:
                s.trace.event("compile", entry=trk.entry,
                              shape="x".join(str(x) for x in trk.shape),
                              seconds=round(trk.elapsed_s, 4))

    def _emit_burst(self, batch: list[_Seq], packed: np.ndarray,
                    k_steps: int, tk: int = 0) -> None:
        """Emit a consumed burst's tokens: packed (2 + 2*tk, K, B) — ids
        f32 + chosen logprobs (+ top-k alternative ids/logprobs when tk).
        Overshoot past a lane's finish is discarded; each consumed input
        token's block registration happens as its KV becomes
        attributable (shared by the sync and pipelined paths so their
        stop/overshoot semantics can never diverge). Emission is
        BATCHED: one EngineOutput (one queue wakeup, one dict) per lane
        per burst — at b48×K32 the per-token version was 1536 outputs
        per sync and measurably the engine's host bottleneck."""
        with self._span("emit"):
            sampled = packed[0].astype(np.int32)     # (K, B)
            logprobs = packed[1]                     # (K, B)
            tk_ids = tk_lps = None
            if tk:
                tk_ids = packed[2:2 + tk].astype(np.int32)   # (tk, K, B)
                tk_lps = packed[2 + tk:2 + 2 * tk]
            for i, s in enumerate(batch):
                if s.finished or s not in self._running:
                    continue  # whole burst is overshoot for this lane
                topk_fn = None
                if tk and s.wants_topk:
                    w = min(s.req.sampling.top_logprobs, tk)

                    def topk_fn(k, _i=i, _w=w):
                        return _topk_list(tk_ids[:, k, _i], tk_lps[:, k, _i],
                                          _w)

                self._emit_lane(s, sampled[:, i], logprobs[:, i], topk_fn)

    def _pp_prefill_all(self, pending: list[_Seq],
                        offsets: dict[int, int]):
        """Pipeline-parallel prefill of a pending wave: one
        pp_prefill_paged call over a (B_pad, T_pad) padded batch —
        chunks flow through the stages as GPipe microbatches and each
        stage writes its layer slice's paged KV. Shapes are bucketed
        (pow2 lanes × pow2-of-chunk tokens, floor n_stages chunks) so
        the compile count stays bounded like the chunk-loop path's."""
        from dynamo_tpu.models.llama_pp import pp_prefill_paged

        cfg, mcfg = self.config, self.model_cfg
        n_stages = cfg.pp_mesh.shape["pp"]
        chunk = min(cfg.prefill_chunk, 128)
        longest = max(len(s.prompt) - offsets[id(s)] for s in pending)
        t_pad = _next_pow2(max(longest, chunk * n_stages), chunk,
                           1 << 30)
        b_pad = _next_pow2(len(pending), 1, cfg.max_batch_size)
        max_pages = mcfg.max_pages_per_seq
        tokens = np.zeros((b_pad, t_pad), dtype=np.int32)
        tables = np.zeros((b_pad, max_pages), dtype=np.int32)
        cached = np.zeros(b_pad, dtype=np.int32)
        seq_lens = np.zeros(b_pad, dtype=np.int32)
        for i, s in enumerate(pending):
            off = offsets[id(s)]
            new = s.prompt[off:]
            tokens[i, :len(new)] = new
            tables[i, :len(s.pages)] = s.pages
            cached[i] = off
            seq_lens[i] = len(s.prompt)
        logits, self.k_cache, self.v_cache = pp_prefill_paged(
            self.params, self.k_cache, self.v_cache,
            jax.numpy.asarray(tokens), jax.numpy.asarray(tables),
            cached, seq_lens, mcfg, cfg.pp_mesh, chunk)
        last_logits = {id(s): (logits, i) for i, s in enumerate(pending)}
        return self.k_cache, self.v_cache, last_logits

    def _sp_bulk_prefill(self, pending: list[_Seq],
                         offsets: dict[int, int]) -> None:
        """Ring-attention bulk prefill for long NOVEL prompts: the first
        page-and-ring-aligned t_sp < len(prompt) tokens run sequence-
        parallel (models/llama_sp.py), the KV pages are scattered into
        the cache device-side, and `offsets` advances so the normal chunk
        loop finishes the tail and produces the last-token logits.

        Prompts with a cached prefix are skipped: the ring only covers
        its own span, so queries inside it could not attend cached KV."""
        from dynamo_tpu.models.llama_sp import sp_prefill

        cfg, mcfg = self.config, self.model_cfg
        sp = cfg.sp_mesh.shape["sp"]
        unit = sp * mcfg.page_size
        if cfg.sp_layout == "zigzag":
            unit *= 2
        for s in pending:
            if offsets[id(s)] != 0:
                continue
            if len(s.prompt) - offsets[id(s)] < cfg.sp_threshold:
                continue
            m = (len(s.prompt) - 1) // unit
            if m <= 0:
                continue
            # pow2 multiples of the ring unit: compile count stays
            # logarithmic in prompt length (the bulk covers >= half the
            # prompt; the chunk loop absorbs the rest)
            t_sp = unit * (1 << (m.bit_length() - 1))
            toks = jnp.asarray(
                np.asarray(s.prompt[:t_sp], dtype=np.int32))[None]
            _, k_all, v_all = sp_prefill(self._sp_params, toks, mcfg,
                                         cfg.sp_mesh,
                                         layout=cfg.sp_layout,
                                         kv_order="ring",
                                         tp_axis=self._sp_tp)
            # land the sequence-sharded KV on the cache's own sharding
            # and scatter it into this sequence's pages. kv_order="ring":
            # un-permuting BEFORE the reshard would all-gather full-T KV
            # onto every ring chip; instead permute post-reshard, where
            # T is no longer sp-sharded
            if self._sp_tp is not None:
                # tp-sharded cache: reshard (L, T, KVH, D) from
                # (seq over sp, heads over tp) to the cache layout
                # (heads over the engine mesh's tp, T whole) — one
                # all-to-all-ish collective, inserted by XLA
                from jax.sharding import NamedSharding, PartitionSpec

                tgt = NamedSharding(cfg.mesh,
                                    PartitionSpec(None, None, "tp", None))
                k_all, v_all = jax.device_put(
                    (k_all[:, 0], v_all[:, 0]), tgt)
            else:
                dev = list(self.k_cache[0].devices())[0]
                k_all, v_all = jax.device_put(
                    (k_all[:, 0], v_all[:, 0]), dev)
            if cfg.sp_layout == "zigzag":
                from dynamo_tpu.engine.ring_attention import (
                    zigzag_permutation,
                )

                _, inv = zigzag_permutation(t_sp, sp)
                k_all, v_all = k_all[:, inv], v_all[:, inv]
            ids = jnp.asarray(np.asarray(
                s.pages[:t_sp // mcfg.page_size], dtype=np.int32))
            self.k_cache, self.v_cache = _sp_writeback(
                self.k_cache, self.v_cache, k_all, v_all, ids,
                mcfg.page_size)
            offsets[id(s)] = t_sp

    def _chunk_rounds(self, params_, model_cfg, kc, vc, seqs, offsets,
                      tokens_of, target_len_of, on_done=None):
        """Batched prefill chunk rounds over `seqs` until every seq's
        offset reaches target_len_of(s). tokens_of(s) supplies the token
        list offsets index into. Returns (kc, vc, final-round logits per
        seq id). Shared by prompt prefill (target AND draft) and the
        draft catch-up replay, so bucketing/compile shapes can't diverge
        between them.

        on_done({id(s): logits}), when given, is called behind a round
        that ended some sequences while others have rounds to go (the
        last round's are the caller's), and the rounds then go by
        sequence: sequences in their last chunk share a round, ahead of
        the rest; a sequence with more chunks to go takes its rounds
        alone, in the order of `seqs`. A full chunk fills the MXU, so a
        round of two such sequences takes twice as long and each would
        wait for the sum of both prompts (PERF.md §6, PR 28)."""
        last_logits: dict[int, Any] = {}
        chunk = self.config.prefill_chunk
        while True:
            ready = [s for s in seqs if offsets[id(s)] < target_len_of(s)]
            if not ready:
                break
            if on_done is not None:
                ending = [s for s in ready
                          if target_len_of(s) - offsets[id(s)] <= chunk]
                ready = ending or ready[:1]
            kc, vc, done, _ = self._chunk_round_once(
                params_, model_cfg, kc, vc, ready, offsets, tokens_of,
                target_len_of)
            last_logits.update(done)
            if on_done is not None and done and any(
                    offsets[id(s)] < target_len_of(s) for s in seqs):
                on_done(done)
        return kc, vc, last_logits

    def _prefill_width(self, n: int) -> int:
        """Compile-bounded prefill batch width for an n-sequence round:
        pow2 (compiles stay bounded to log2 widths per T bucket while
        low-concurrency prefill — compute-bound, unlike decode — avoids
        paying max_batch_size× the FLOPs), or the configured
        prefill_batch_widths ladder."""
        cfg = self.config
        if cfg.prefill_batch_widths:
            bp = next((w for w in cfg.prefill_batch_widths if w >= n),
                      cfg.prefill_batch_widths[-1])
            return min(bp, cfg.max_batch_size)
        return _next_pow2(n, 1, cfg.max_batch_size)

    def _token_bucket(self, n: int, model_cfg=None) -> int:
        """Prefill token bucket for an n-token chunk: the static
        _next_bucket ladder, refined by any flight-control rungs the
        bucket autotuner has applied (engine/bucketing.py). Unarmed
        (bucket_ladder None, the default) this is exactly _next_bucket.
        model_cfg defaults to the target model's (draft rounds pass the
        draft model's, whose page size may differ)."""
        cfg = self.config
        mcfg = self.model_cfg if model_cfg is None else model_cfg
        base = _next_bucket(n, cfg.min_prefill_bucket, cfg.prefill_chunk,
                            align=mcfg.page_size)
        if self.bucket_ladder is not None:
            return self.bucket_ladder.bucket_for(
                n, base, lo=cfg.min_prefill_bucket, align=mcfg.page_size)
        return base

    # -- ragged dispatch ----------------------------------------------------

    def _ragged_active(self) -> bool:
        """True when this engine routes batches through the flat-token
        ragged entry (`DYN_ATTENTION_IMPL=ragged` / set_attention_impl).
        Spec (draft) and pipeline-parallel engines keep their dedicated
        entries — their burst structure is the feature, not padding."""
        return (ragged_enabled() and self.config.pp_mesh is None
                and self.draft_params is None)

    @property
    def ragged_active(self) -> bool:
        """Controller-facing alias (control/controllers.py gates the
        BucketAutotuner off a `ragged_active` attribute so the perf-sim
        shims and MockEngine can expose the same signal)."""
        return self._ragged_active()

    def _ragged_bucket(self, n: int) -> int:
        """Total-token bucket for a ragged round. Below
        min_prefill_bucket the bucket is plain pow2 — decode-tail
        rounds (a few lanes, no chunks) match the legacy width family
        instead of padding one lane to a 16-row floor. Above it, the
        {lo·2^k, lo·3·2^(k-1)} ladder with NO page alignment (flat rows
        scatter per-row KV, so a misaligned Tb disables nothing) and no
        prefill_chunk cap (the round may also carry up to
        max_batch_size decode rows)."""
        lo = self.config.min_prefill_bucket
        if n < lo:
            return _next_pow2(n, 1, lo)
        return _next_bucket(n, lo, 1 << 30)

    def _ragged_core(self, kc, vc, picks: list[_Seq], offsets,
                     chunk_lens: list[int], tokens_of,
                     batch: list[_Seq], tk: int):
        """Build + dispatch ONE flat-token ragged round (device-blocking
        — call under the device lock, in a thread): each pick's capped
        chunk becomes `chunk_lens[i]` flat rows; when decode lanes ride
        the round they occupy a FIXED block of max_batch_size rows
        (invalid rows mark empty lanes) — the decode-lane count spans a
        tiny bounded range where a recompile costs far more than the
        padded rows (the same trade the legacy fixed-width burst makes),
        while chunk tokens, the unbounded axis, stay exact-length.
        Padding rows fill to the total-token bucket. The compile shape
        is `(t_bucket, tk)` — lane-table width, ch_rows and the
        sampling arrays are fixed at max_batch_size, so decode width,
        chunk count, k_steps and alignment all vanish from the shape
        zoo (tk stays: top-k logprobs change the packed output width,
        a genuinely different program). Registers the dispatch with the
        memory ledger (the kernel workspace + caches attribute to the
        `ragged_step` entry).
        Returns (packed np (2+2tk, 1, bmax), ch_logits (device, row i =
        pick i's last chunk token), kc, vc)."""
        cfg, mcfg = self.config, self.model_cfg
        P = mcfg.page_size
        bmax = cfg.max_batch_size
        with self._span("prefill_prep"):
            total = sum(chunk_lens) + (bmax if batch else 0)
            tb = self._ragged_bucket(total)
            toks = np.zeros(tb, dtype=np.int32)
            poss = np.zeros(tb, dtype=np.int32)
            pages = np.zeros(tb, dtype=np.int32)
            offs = np.zeros(tb, dtype=np.int32)
            valid = np.zeros(tb, dtype=bool)
            lanes = np.zeros(tb, dtype=np.int32)
            # lane-table rows 0..bmax-1 = chunk picks, bmax..2*bmax-1 =
            # decode lanes; the width is a constant so it never buckets
            lane_tables = np.zeros((2 * bmax, mcfg.max_pages_per_seq),
                                   dtype=np.int32)
            ch_rows = np.zeros(bmax, dtype=np.int32)
            d_rows = np.zeros(bmax, dtype=np.int32)
            seeds = np.zeros(bmax, dtype=np.uint32)
            steps = np.zeros(bmax, dtype=np.uint32)
            temps = np.zeros(bmax, dtype=np.float32)
            top_ps = np.ones(bmax, dtype=np.float32)
            top_ks = np.zeros(bmax, dtype=np.int32)
            r = 0
            for i, s in enumerate(picks):
                off, n = offsets[id(s)], chunk_lens[i]
                seq_pages = np.asarray(s.pages, dtype=np.int32)
                lane_tables[i, :len(s.pages)] = seq_pages
                p_arr = np.arange(off, off + n, dtype=np.int32)
                toks[r:r + n] = tokens_of(s)[off:off + n]
                poss[r:r + n] = p_arr
                pages[r:r + n] = seq_pages[p_arr // P]
                offs[r:r + n] = p_arr % P
                valid[r:r + n] = True
                lanes[r:r + n] = i
                r += n
                ch_rows[i] = r - 1
            if batch:
                # fixed decode block: row r+j is lane j, valid only for the
                # lanes actually present; d_rows for empty slots point at
                # their own (masked, zero-output) padding row
                d_rows[:] = r + np.arange(bmax, dtype=np.int32)
            for j, s in enumerate(batch):
                li = bmax + j
                rj = r + j
                lane_tables[li, :len(s.pages)] = s.pages
                toks[rj] = s.next_token
                poss[rj] = s.pos
                pages[rj] = s.pages[s.pos // P]
                offs[rj] = s.pos % P
                valid[rj] = True
                lanes[rj] = li
                seeds[j] = s.seed
                steps[j] = s.generated
                temps[j] = s.req.sampling.temperature
                top_ps[j] = s.req.sampling.top_p
                top_ks[j] = s.req.sampling.top_k

        trk = self.metrics.compile.track("ragged_step", (tb, tk))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)
        with trk:
            packed, ch_logits, kc, vc = self._mesh_dispatch(
                trk, ragged_prefill_decode,
                self.params, kc, vc,
                jax.numpy.asarray(toks), jax.numpy.asarray(poss),
                jax.numpy.asarray(pages), jax.numpy.asarray(offs),
                jax.numpy.asarray(valid), jax.numpy.asarray(lanes),
                jax.numpy.asarray(lane_tables),
                jax.numpy.asarray(ch_rows), jax.numpy.asarray(d_rows),
                jax.numpy.asarray(seeds), jax.numpy.asarray(steps),
                jax.numpy.asarray(temps), jax.numpy.asarray(top_ps),
                jax.numpy.asarray(top_ks), mcfg, tk,
                span_tokens=sum(chunk_lens) + len(batch), temps=temps)
            # ONE host sync; chunk logits stay on device for the
            # first-token sampler
            packed = self._host_sync(packed)
        if picks:
            self.metrics.prefill_chunk.observe(trk.elapsed_s)
        rec = self.step_recorder
        if rec is not None:
            # the whole point: work is the total-token bucket, not a
            # (width x steps) + (bp x t_bucket) rectangle — padding is
            # the bucket tail plus any empty decode-block slots
            rec.record("ragged_step", trk.shape, trk.elapsed_s,
                       good_tokens=sum(chunk_lens) + len(batch),
                       work_tokens=tb,
                       lanes=len(picks) + len(batch), width=len(batch),
                       tokens=len(batch), compiled=trk.compiled)
        self._mark_decode_compile(batch, trk)
        if picks:
            self._trace_chunk(picks, chunk_lens, trk, mixed=bool(batch))
        return packed, ch_logits, kc, vc

    async def _ragged_mixed(self, picks: list[_Seq], offsets, caps,
                            batch: list[_Seq]) -> bool:
        """The ragged replacement for `_mixed_step`: chunk rows + the
        fixed decode block in ONE flat dispatch. Decode lanes advance
        one token per round (the scheduler loop supplies the cadence) —
        vs the fused k_steps burst this trades more dispatches for a
        compile shape that varies only with the chunk-token total."""
        chunk_lens = [caps[id(s)] for s in picks]
        tk = self.TOPK_WIDTH if any(s.wants_topk for s in batch) else 0

        def dispatch():
            return self._ragged_core(
                self.k_cache, self.v_cache, picks, offsets, chunk_lens,
                lambda s: s.prompt, batch, tk)

        async with self._device_lock:
            packed, ch_logits, self.k_cache, self.v_cache = \
                await asyncio.to_thread(dispatch)
        self.metrics.mixed_steps.inc()
        self.metrics.decode_steps_during_prefill.inc(1)
        done_logits: dict[int, Any] = {}
        for i, s in enumerate(picks):
            offsets[id(s)] += chunk_lens[i]
            s.prefill_pos = offsets[id(s)]
            if s.prefill_pos >= len(s.prompt):
                done_logits[id(s)] = (ch_logits, i)
        self._emit_burst(batch, packed, 1, tk)
        await self._finish_first_tokens(picks, done_logits)
        return True

    async def _ragged_decode(self, batch: list[_Seq], tk: int) -> bool:
        """Decode-only ragged round: one flat row per lane, one token
        per lane per dispatch."""
        if any(not s.prefilled for s in self._running):
            self.metrics.decode_steps_during_prefill.inc(1)

        def dispatch():
            return self._ragged_core(self.k_cache, self.v_cache, [], {},
                                     [], None, batch, tk)

        async with self._device_lock:
            packed, _, self.k_cache, self.v_cache = \
                await asyncio.to_thread(dispatch)
        self._emit_burst(batch, packed, 1, tk)
        return True

    def _chunk_round_once(self, params_, model_cfg, kc, vc, ready,
                          offsets, tokens_of, target_len_of, caps=None):
        """ONE batched prefill chunk round: group by page-alignment,
        pick the pow2 batch width and T bucket, run prefill_batch, and
        advance the offsets. `caps` (optional {id(s): max_tokens})
        bounds each sequence's chunk below cfg.prefill_chunk — the
        budgeted scheduler's token budget. Returns (kc, vc,
        {id(s): last-token logits} for sequences whose offset REACHED
        target this round, tokens consumed). When the ragged path is
        active (target model only — the draft keeps its entry), the
        round dispatches flat rows instead: no alignment grouping, no
        width/T-bucket rectangle."""
        cfg = self.config
        if params_ is self.params and self._ragged_active():
            active = ready[:cfg.max_batch_size]
            chunk_lens = [min(target_len_of(s) - offsets[id(s)],
                              cfg.prefill_chunk,
                              caps[id(s)] if caps else cfg.prefill_chunk)
                          for s in active]
            packed_, ch_logits, kc, vc = self._ragged_core(
                kc, vc, active, offsets, chunk_lens, tokens_of, [], 0)
            done: dict[int, Any] = {}
            for i, s in enumerate(active):
                offsets[id(s)] += chunk_lens[i]
                if offsets[id(s)] >= target_len_of(s):
                    done[id(s)] = (ch_logits, i)
            return kc, vc, done, sum(chunk_lens)
        # rounds are grouped by page-alignment of the cached
        # offset: mid-page starts (disagg imports) need the row
        # write path — batching them with aligned lanes would
        # drag everyone onto it
        with self._span("prefill_prep"):
            aligned_s = [s for s in ready
                         if offsets[id(s)] % model_cfg.page_size == 0]
            active = aligned_s or ready
            aligned = bool(aligned_s)
            bp = self._prefill_width(len(active))
            active = active[:bp]
            chunk_lens = [min(target_len_of(s) - offsets[id(s)],
                              cfg.prefill_chunk,
                              caps[id(s)] if caps else cfg.prefill_chunk)
                          for s in active]
            t_bucket = self._token_bucket(max(chunk_lens), model_cfg)
            toks = np.zeros((bp, t_bucket), dtype=np.int32)
            tables = np.zeros((bp, model_cfg.max_pages_per_seq),
                              dtype=np.int32)
            cached = np.zeros(bp, dtype=np.int32)
            seq_lens = np.zeros(bp, dtype=np.int32)
            for i, s in enumerate(active):
                off, n = offsets[id(s)], chunk_lens[i]
                toks[i, :n] = tokens_of(s)[off:off + n]
                tables[i, :len(s.pages)] = s.pages
                cached[i] = off
                seq_lens[i] = off + n
        trk = self.metrics.compile.track(
            "prefill_draft" if (self.draft_params is not None
                                and params_ is self.draft_params)
            else "prefill", (bp, t_bucket, int(aligned)))
        led = self.memory_ledger
        if led is not None:
            led.on_dispatch(trk.entry, trk.shape, compiled=trk.compiled)
        with trk:
            logits_b, kc, vc = self._mesh_dispatch(
                trk, self._prefill_batch,
                params_, kc, vc,
                jax.numpy.asarray(toks), jax.numpy.asarray(tables),
                jax.numpy.asarray(cached), jax.numpy.asarray(seq_lens),
                model_cfg, aligned, span_tokens=sum(chunk_lens),
                **self._slot_kw(active, bp))
        if self.recurrent:
            # first chunks: a slot started from zero
            self.metrics.state_resets.inc(
                sum(1 for s in active if offsets[id(s)] == 0))
        self.metrics.prefill_chunk.observe(trk.elapsed_s)
        rec = self.step_recorder
        if rec is not None:
            # logits stay on device for the first-token sampler — no
            # host sync here, so this is dispatch wall time only
            rec.record(trk.entry, trk.shape, trk.elapsed_s,
                       good_tokens=sum(chunk_lens),
                       work_tokens=bp * t_bucket, lanes=len(active),
                       width=bp, compiled=trk.compiled, synced=False)
        self._trace_chunk(active, chunk_lens, trk)
        done: dict[int, Any] = {}
        for i, s in enumerate(active):
            offsets[id(s)] += chunk_lens[i]
            if offsets[id(s)] >= target_len_of(s):
                done[id(s)] = (logits_b, i)
        return kc, vc, done, sum(chunk_lens)

    # -- guided decoding ----------------------------------------------------

    # Widest top-k alternatives the packed burst carries (OpenAI allows
    # top_logprobs<=20 but >8 is vanishingly rare; the width is a compile
    # shape, so it is fixed and requests are capped at the protocol
    # layer). Lanes that don't ask pay nothing: the no-topk variant is a
    # separate compiled burst.
    TOPK_WIDTH = 8

    # raw ITL sample FIFO cap (exact percentiles for bench; the
    # histogram in perf["itl_hist"] is unbounded and wire-published)
    ITL_SAMPLE_CAP = 8192

    MAX_GUIDED_GRAMMARS = 32
    GUIDED_STOP_WIDTH = 8
    # ceiling on the stacked (G, S, V) device tables — a handful of big
    # JSON-schema grammars on a 128k vocab must fail the REQUEST, not
    # OOM the chip mid-serving
    GUIDED_TABLE_MAX_BYTES = 1 << 30

    async def _compile_guided(self, spec: dict, req) -> Any:
        """Compile (or fetch cached) DFA tables for a guided spec. The
        regex→DFA→token-table build can take seconds for big grammars —
        it runs in a thread and is cached by the spec's canonical JSON.
        Tables are EOS-agnostic (stop tokens overlay per lane), so the
        spec alone is a sound cache key."""
        from dynamo_tpu.runtime.compute import run_cpu

        if callable(self._guided_vocab):
            # lazy: the O(vocab) token-bytes map is only built when the
            # first guided request arrives, not at engine startup.
            # CPU-bound ⇒ the bounded compute pool (runtime/compute.py),
            # not the unbounded to_thread executor the DEVICE-blocking
            # dispatches use. Serialized: N concurrent first guided
            # requests must not build the O(vocab) map N times.
            if not hasattr(self, "_guided_vocab_lock"):
                self._guided_vocab_lock = asyncio.Lock()
            async with self._guided_vocab_lock:
                if callable(self._guided_vocab):
                    self._guided_vocab = await run_cpu(
                        self._guided_vocab)
        if self._guided_vocab is None:
            raise ValueError(
                "engine has no tokenizer vocabulary (token_bytes) — "
                "guided decoding unavailable")
        key = self._guided_key(spec)
        tables = self._guided_tables.get(key)
        if tables is not None:
            return tables
        from dynamo_tpu.llm.guided import compile_guided

        tables = await run_cpu(compile_guided, spec, self._guided_vocab)
        # re-check: a concurrent compile of the same spec may have won
        # the race while we were in the thread — double-assigning the
        # slot would alias a later grammar onto it
        if key not in self._guided_tables:
            if (len(self._guided_tables) >= self.MAX_GUIDED_GRAMMARS
                    or self._guided_stack_bytes(tables)
                    > self.GUIDED_TABLE_MAX_BYTES):
                self._evict_guided_unused()
            if len(self._guided_tables) >= self.MAX_GUIDED_GRAMMARS:
                raise ValueError(
                    "too many distinct guided grammars in flight")
            if self._guided_stack_bytes(tables) \
                    > self.GUIDED_TABLE_MAX_BYTES:
                raise ValueError(
                    f"guided grammar tables would exceed "
                    f"{self.GUIDED_TABLE_MAX_BYTES >> 20} MiB on device")
            self._guided_tables[key] = tables
            self._guided_slots[key] = len(self._guided_slots) + 1
            self._guided_stack = None      # restack with the new grammar
        return self._guided_tables[key]

    def _guided_stack_bytes(self, extra=None) -> int:
        """Projected device bytes of the stacked tables if `extra` joins
        the cache (pow2 padding on both axes included)."""
        V = self.model_cfg.vocab_size
        all_tables = list(self._guided_tables.values())
        if extra is not None:
            all_tables.append(extra)
        s_max = max([t.num_states for t in all_tables] or [1])
        s_pad = _next_pow2(s_max, 1, 1 << 15)
        g_pad = _next_pow2(len(all_tables) + 1, 1,
                           2 * self.MAX_GUIDED_GRAMMARS)
        return g_pad * s_pad * (2 * V + (V + 7) // 8 + 1)

    @staticmethod
    def _guided_key(spec: dict) -> str:
        """Canonical cache key for a guided spec. The pending-ref,
        eviction, and slot machinery all key on this — every lookup must
        go through here so they can never disagree."""
        import json as _json

        return _json.dumps(spec, sort_keys=True)

    def _penalty_arrays(self, lanes: list, width: int):
        """(rep, freq, pres (width,) f32, prompt_counts, out_counts
        (width, V) i32) for a wave's lanes — THE one packing all three
        penalty consumers (prefill first-token, constrained burst, spec
        burst) build from, so penalty semantics can never diverge
        between paths. Lanes without penalties get exact no-op values
        (rep=1, freq/pres=0, zero histograms)."""
        V = self.model_cfg.vocab_size
        rep = np.ones(width, dtype=np.float32)
        freq = np.zeros(width, dtype=np.float32)
        pres = np.zeros(width, dtype=np.float32)
        pc = np.zeros((width, V), dtype=np.int32)
        oc = np.zeros((width, V), dtype=np.int32)
        for i, s in enumerate(lanes):
            sp = s.req.sampling
            rep[i] = sp.repetition_penalty
            freq[i] = sp.frequency_penalty
            pres[i] = sp.presence_penalty
            if s.has_penalties:
                pc[i] = s.prompt_hist(V)
                for t, c in s.out_counter.items():
                    if 0 <= t < V:
                        oc[i, t] = c
        return rep, freq, pres, pc, oc

    def _guided_lane_arrays(self, batch: list, b: int):
        """(g_ids, g_states, stop_ids) numpy arrays for a burst's lanes
        (slots must already be registered/settled for the batch) — the
        ONE packing both the constrained and the spec-guided bursts use,
        so their slot/state/stop semantics can never diverge."""
        g_ids = np.zeros(b, dtype=np.int32)
        g_states = np.zeros(b, dtype=np.int32)
        stop_ids = np.full((b, self.GUIDED_STOP_WIDTH), -1,
                           dtype=np.int32)
        for i, s in enumerate(batch):
            g_ids[i] = self._guided_slot_of(s)
            g_states[i] = s.guided_state
            for j, t in enumerate(self._guided_stop_ids(s)):
                stop_ids[i, j] = t
        return g_ids, g_states, stop_ids

    def _guided_unpend(self, key: str) -> None:
        """Release one pending ref taken in generate()."""
        n = self._guided_pending.get(key, 0) - 1
        if n <= 0:
            self._guided_pending.pop(key, None)
        else:
            self._guided_pending[key] = n

    def _evict_guided_unused(self) -> None:
        """Drop cached grammars no active sequence references, and
        renumber slots compactly (the device stack is rebuilt). Grammars
        with a pending ref (request between compile and _waiting.append)
        count as active."""
        active = {
            self._guided_key(s.req.sampling.guided)
            for s in self._running + self._waiting
            if s.guided is not None}
        active |= set(self._guided_pending)
        self._guided_tables = {k: v for k, v in
                               self._guided_tables.items() if k in active}
        self._guided_slots = {k: i + 1 for i, k in
                              enumerate(self._guided_tables)}
        self._guided_stack = None

    def _guided_device_stack(self):
        """(bits (G, S, ceil(V/8)) u8, next (G, S, V) i16, eos_ok (G, S)
        bool) covering slot 0 (trivial all-allowed) + every compiled
        grammar, padded to pow2 G and S so compile shapes stay
        bounded."""
        if self._guided_stack is not None:
            return self._guided_stack
        V = self.model_cfg.vocab_size
        bv = (V + 7) // 8
        tables = sorted(self._guided_tables.items(),
                        key=lambda kv: self._guided_slots[kv[0]])
        s_max = max([t.num_states for _, t in tables] or [1])
        s_pad = _next_pow2(s_max, 1, 1 << 15)
        g_pad = _next_pow2(len(tables) + 1, 1,
                           2 * self.MAX_GUIDED_GRAMMARS)
        bits = np.zeros((g_pad, s_pad, bv), dtype=np.uint8)
        nxt = np.zeros((g_pad, s_pad, V), dtype=np.int16)
        eos_ok = np.zeros((g_pad, s_pad), dtype=bool)
        bits[0, :, :] = 0xFF               # slot 0: everything allowed
        for key, t in tables:
            slot = self._guided_slots[key]
            s = t.num_states
            bits[slot, :s] = t.allowed_bits[:, :bv]
            nxt[slot, :s] = t.next_state[:, :V]
            eos_ok[slot, :s] = t.eos_ok
        self._guided_stack = (jax.numpy.asarray(bits),
                              jax.numpy.asarray(nxt),
                              jax.numpy.asarray(eos_ok))
        return self._guided_stack

    def _guided_slot_of(self, seq: _Seq) -> int:
        if seq.guided is None:
            return 0
        key = self._guided_key(seq.req.sampling.guided)
        slot = self._guided_slots.get(key)
        if slot is None:
            # backstop: the grammar was evicted between this seq's
            # compile and now (shouldn't happen with pending refs, but a
            # KeyError here would reach the scheduler catch-all and
            # _fail_all every in-flight request). The seq still holds its
            # compiled tables — re-register them. Evicting unused first
            # keeps the cache inside the admission caps: active distinct
            # specs can never exceed MAX_GUIDED_GRAMMARS (each passed
            # admission while its peers were active), so after eviction
            # the insert fits the count cap; the byte cap depends on the
            # cache's current size mix and must be re-checked (callers
            # fail only the offending lane on ValueError).
            self._evict_guided_unused()
            if self._guided_stack_bytes(seq.guided) \
                    > self.GUIDED_TABLE_MAX_BYTES:
                raise ValueError(
                    f"guided grammar tables would exceed "
                    f"{self.GUIDED_TABLE_MAX_BYTES >> 20} MiB on device "
                    f"(re-registration after eviction)")
            self._guided_tables[key] = seq.guided
            self._guided_slots[key] = slot = len(self._guided_slots) + 1
            self._guided_stack = None
            logger.warning("guided grammar re-registered after eviction "
                           "(slot %d)", slot)
        return slot

    def _guided_stop_ids(self, seq: _Seq) -> list[int]:
        ids = list(seq.req.stop.stop_token_ids or [])[
            :self.GUIDED_STOP_WIDTH]
        return ids or [self._guided_eos]

    def _guided_allowed_row(self, tables, seq: _Seq,
                            vocab: int) -> np.ndarray:
        bits = np.unpackbits(tables.allowed_bits[seq.guided_state],
                             bitorder="little")
        row = bits[:vocab].astype(bool)
        if tables.eos_ok[seq.guided_state]:
            for t in self._guided_stop_ids(seq):
                if 0 <= t < vocab:
                    row[t] = True
        return row

    async def _draft_catchup(self, lanes: list[_Seq]) -> None:
        """Replay tokens the draft cache is missing (positions
        draft_pos..pos-1, known from token_seq) through draft prefill
        rounds."""

        def rounds():
            offsets = {id(s): s.draft_pos for s in lanes}
            self.dk_cache, self.dv_cache, _ = self._chunk_rounds(
                self.draft_params, self.config.draft_model,
                self.dk_cache, self.dv_cache, lanes, offsets,
                tokens_of=lambda s: s.token_seq.tokens,
                target_len_of=lambda s: s.pos)

        async with self._device_lock:
            await asyncio.to_thread(rounds)
        for s in lanes:
            s.draft_pos = s.pos

    async def _pipeline_consume(self) -> bool:
        """Land the in-flight decode burst: optionally dispatch the NEXT
        burst first (inputs sliced on device from the in-flight packed
        output — speculation is sound because the fused loop already
        feeds sampled tokens forward on device; the host would compute
        identical inputs), then sync, emit, and release pages deferred
        from the previous generation."""
        cfg, mcfg = self.config, self.model_cfg
        inf = self._inflight
        k = inf["k"]
        batch = inf["batch"]
        nxt = None
        # speculate only when nothing can change the batch: slots full
        # (no admission), every lane alive/uncancelled/plain, no draft
        # engine (it would want a spec burst instead). "Nothing can
        # change the batch" holds in TWO states: slots full (arrivals
        # must queue), or nothing waiting AND every running lane is in
        # this burst (an arrival during the speculative burst gets
        # admitted next pass, which flips this check False and drains
        # the pipeline before the batch is rebuilt). The second state
        # pipelines phase TAILS and low-concurrency serving — r5: the
        # slots-full-only guard left every partial batch unpipelined,
        # paying the full sync per burst exactly when per-request
        # latency is most visible.
        def can_spec() -> bool:
            return ((len(self._running) >= cfg.max_batch_size
                     or (not self._waiting
                         and len(self._running) == len(batch)))
                    and self.draft_params is None
                    # a wave prefilled behind this burst joins the next
                    # one from the device (_chain_burst), not the one
                    # after it
                    and not inf["firsts"]
                    and all(s in self._running and not s.ctx.is_cancelled()
                            and not s.needs_constrained for s in batch)
                    # every lane will hit max_tokens within the burst
                    # being consumed ⇒ the speculative burst would be
                    # 100% overshoot AND the next wave's prefill would
                    # queue behind its wasted device time
                    and any(s.max_tokens - s.generated > k
                            for s in batch))

        async def speculate() -> None:
            nonlocal nxt
            if not (can_spec() and all(
                    self._cover(s, s.pos + 2 * k - 1) for s in batch)):
                return
            b = cfg.max_batch_size
            with self._span("decode_prep"):
                page_tables2 = np.zeros((b, mcfg.max_pages_per_seq),
                                        dtype=np.int32)
                for i, s in enumerate(batch):
                    page_tables2[i, :len(s.pages)] = s.pages
            # the speculative burst runs the program of the burst
            # it follows, under that burst's (entry, shape)
            trk2 = self.metrics.compile.track(
                "decode_burst", (b, k, inf.get("tk", 0)))

            def dispatch2():
                # sliced on device while the in-flight burst still
                # runs: no device idle at stake, so under no span
                tokens2 = inf["packed"][0, k - 1].astype(jnp.int32)
                with trk2:
                    return self._mesh_dispatch(
                        trk2, self._decode_multi_step,
                        self.params, self.k_cache, self.v_cache,
                        tokens2,
                        jax.numpy.asarray(inf["positions"] + k),
                        jax.numpy.asarray(page_tables2),
                        jax.numpy.asarray(inf["valid"]),
                        jax.numpy.asarray(inf["seeds"]),
                        jax.numpy.asarray(inf["steps"] + k),
                        jax.numpy.asarray(inf["temps"]),
                        jax.numpy.asarray(inf["top_ps"]),
                        jax.numpy.asarray(inf["top_ks"]),
                        mcfg, k, topk_lp=inf.get("tk", 0),
                        span_tokens=len(batch) * k, temps=inf["temps"],
                        **self._slot_kw(batch, b))

            rec = self.step_recorder
            t_d2 = time.perf_counter() if rec is not None else 0.0
            async with self._device_lock:
                packed2, self.k_cache, self.v_cache = \
                    await asyncio.to_thread(dispatch2)
            if rec is not None:
                rec.record("decode_burst",
                           (b, k, inf.get("tk", 0)),
                           time.perf_counter() - t_d2,
                           good_tokens=len(batch) * k,
                           work_tokens=b * k, lanes=len(batch),
                           width=b, tokens=len(batch) * k,
                           synced=False)
            self.metrics.pipelined_bursts.inc()
            nxt = {"k": k, "batch": batch, "packed": packed2,
                   "positions": inf["positions"] + k,
                   "valid": inf["valid"], "seeds": inf["seeds"],
                   "steps": inf["steps"] + k, "temps": inf["temps"],
                   "top_ps": inf["top_ps"],
                   "top_ks": inf["top_ks"],
                   "tk": inf.get("tk", 0), "deferred": [],
                   "firsts": [], "t0": 0.0}

        # A decoding lane that ended since the last burst was consumed
        # left a lane free and, in a closed loop, a caller on its way
        # back: a successor launched now would have that caller wait it
        # out whole before its prefill. Hold it until most of this burst
        # has passed (by the last burst's device time); the caller
        # admitted meanwhile is prefilled behind THIS burst and joins
        # the next one (can_spec is then false). Nobody came: the
        # successor still goes out ahead of the landing.
        expected, self._lanes_ended = self._lanes_ended, 0
        held = ()
        if (expected and self._burst_s and self._refills_behind_burst
                and len(self._running) < cfg.max_batch_size):
            held = (speculate,
                    inf["t0"] + self._SPEC_HOLD * self._burst_s)
        else:
            await speculate()
        rec = self.step_recorder
        t_sync = time.perf_counter() if rec is not None else 0.0
        if self._refills_behind_burst:
            packed = await self._land_burst(
                inf["packed"], lambda: self._prefill_behind(inf), *held)
        else:
            packed = await asyncio.to_thread(self._host_sync, inf["packed"])
        # did the waves behind this burst take every lane (before its
        # emission frees any)? Then nobody can arrive ahead of their burst
        full = len(self._running) >= cfg.max_batch_size
        now = time.perf_counter()
        self._burst_s = now - inf["t0"]
        if nxt is not None:
            nxt["t0"] = now             # queued behind this one
        if rec is not None:
            # the honest device wait for a pipelined burst: np.asarray
            # round-trip, not block_until_ready;
            # goodput was attributed at dispatch, this is pure timing
            rec.record("burst_sync", (len(batch), k),
                       time.perf_counter() - t_sync,
                       lanes=len(batch), width=cfg.max_batch_size)
        # while the speculative burst runs, finished lanes' pages must
        # not return to the pool (the burst still writes to them)
        self._defer_releases = nxt["deferred"] if nxt is not None else None
        try:
            self._emit_burst(batch, packed, k, inf.get("tk", 0))
        finally:
            self._defer_releases = None
        for pages in inf["deferred"]:
            self.pool.release_sequence(pages)
        self._inflight = nxt
        # the waves prefilled behind this burst (_prefill_behind), their
        # first tokens still on the device
        firsts = inf["firsts"]
        if firsts and nxt is not None:
            nxt["firsts"] = firsts      # launched behind nxt: land with it
        elif firsts:
            # host state is current again: the waves' burst goes out
            # behind their samplers, the emit above under their rounds.
            # With a lane to spare it waits for the first tokens, as
            # after any wave prefilled behind a burst: whoever arrives
            # while they are synced (long prompts: many rounds) is
            # prefilled ahead of the next burst, not behind it
            async with self._device_lock:
                await self._land_first_tokens(firsts, full)
        return True

    # -- lifecycle helpers --------------------------------------------------

    def _emit_lane(self, seq: _Seq, toks, lps,
                   topk_fn: Optional[Callable[[int], list]] = None,
                   append_inputs: bool = True) -> int:
        """Emit up to len(toks) tokens for ONE lane as ONE EngineOutput:
        stop/length conditions are scanned vectorized, per-token host
        side effects (KV-attribution appends, guided DFA advance,
        penalty counters) run only where needed, and the consumer gets
        a single queue wakeup per burst. THE emission definition — the
        prefill, plain/pipelined burst, and spec paths all come through
        here, so stop/overshoot/export semantics can never diverge.
        topk_fn(k) -> alternatives list for burst step k (called only
        for emitted steps). append_inputs=False for prefill: the first
        sampled token has no prior burst input whose KV needs
        attributing to token_seq. Returns the number of tokens
        emitted."""
        limit = min(len(toks), max(seq.max_tokens - seq.generated, 0))
        n_emit = limit
        finish = None
        stop_set = seq.req.stop.stop_token_ids
        if stop_set:
            hits = np.flatnonzero(np.isin(toks[:limit],
                                          list(stop_set)))
            min_toks = seq.req.stop.min_tokens
            for j in hits:
                if seq.generated + int(j) + 1 >= min_toks:
                    n_emit = int(j) + 1
                    finish = FINISH_STOP
                    break
        if finish is None and seq.generated + n_emit >= seq.max_tokens:
            finish = FINISH_LENGTH
        if n_emit <= 0:
            # degenerate (lane already at max_tokens): finish only
            if finish is not None:
                self._finish(seq, finish)
            return 0
        now = time.monotonic()
        if seq.last_emit_t:
            # inter-token latency at the EMISSION boundary — the gap the
            # consumer actually experiences, including any prefill chunk
            # rounds that ran between this lane's bursts (the stall the
            # budgeted scheduler exists to bound)
            gap_ms = (now - seq.last_emit_t) * 1000.0
            self.metrics.itl.observe(gap_ms)
            self.itl_samples.append(gap_ms)
            if len(self.itl_samples) > self.ITL_SAMPLE_CAP:
                del self.itl_samples[:-self.ITL_SAMPLE_CAP]
        elif seq.generated == 0:
            # this lane's FIRST emission: TTFT measured at the source
            self.metrics.ttft.observe(
                max(time.perf_counter() - seq.t_enqueue, 0.0))
            seq.t_first_ns = seq.ctx.stamp(ENGINE)  # the clock's instant
            if seq.trace is not None:
                if seq.t_admit_ns:
                    seq.trace.stage("engine.prefill", seq.t_admit_ns,
                                    seq.t_first_ns,
                                    prompt_tokens=len(seq.prompt),
                                    cached_len=seq.cached_len)
                seq.trace.event("first_token")
        seq.last_emit_t = now
        emit_toks = [int(t) for t in toks[:n_emit]]
        guided = seq.guided
        count = seq.has_penalties
        for t in emit_toks:
            if append_inputs:
                # the step-k input token's KV is now on device
                block = seq.token_seq.append(seq.next_token)
                if block is not None and not self.recurrent:
                    self.pool.register_page(
                        seq.pages[block.block_index], block.seq_hash,
                        block.local_hash, block.parent_seq_hash)
            if guided is not None:
                # authoritative DFA state lives host-side (device lane
                # states are re-seeded from it each burst, so overshoot
                # discards and preemption replays can't desync)
                seq.guided_state = int(
                    guided.next_state[seq.guided_state, t])
            if count:
                seq.out_counter[t] = seq.out_counter.get(t, 0) + 1
            seq.next_token = t
        seq.generated += n_emit
        self.metrics.tokens_emitted.inc(n_emit)
        if self.tenant_metrics is not None and seq.tenant is not None:
            self.tenant_metrics.goodput.inc(n_emit, tenant=seq.tenant)
        out = EngineOutput(token_ids=emit_toks, finish_reason=finish)
        if lps is not None:
            out.log_probs = [float(x) for x in lps[:n_emit]]
        if topk_fn is not None:
            out.top_logprobs = [topk_fn(k) for k in range(n_emit)]
        exported = False
        if finish is not None and \
                (seq.req.kv_transfer_params or {}).get("do_remote_decode"):
            # disagg prefill worker: pin this seq's pages for the decode
            # worker to pull; advertise the transfer in the final frame
            # (handlers.py adds the worker's address; SURVEY §3.3).
            # Pin only the pages holding the seq.pos written tokens —
            # decode-lookahead pages would break the importer's shapes.
            ps = self.model_cfg.page_size
            n_pages = (seq.pos + ps - 1) // ps
            if self._defer_releases is not None:
                self._defer_releases.append(list(seq.pages[n_pages:]))
            else:
                self.pool.release_sequence(seq.pages[n_pages:])
            tid = uuid.uuid4().hex
            self._transfers[tid] = (
                seq.pages[:n_pages], seq.pos,
                time.monotonic() + self.transfer_ttl)
            out.kv_transfer_params = {
                "transfer_id": tid, "prefill_len": seq.pos,
                "worker_id": self.config.worker_id}
            exported = True
        seq.queue.put_nowait(out.to_dict())
        if finish is not None:
            self._finish(seq, finish, emit=False,
                         release_pages=not exported)
        return n_emit

    def _finish(self, seq: _Seq, reason: str, emit: bool = True,
                release_pages: bool = True) -> None:
        if seq.trace is not None:
            end_ns = time.time_ns()
            if seq.t_first_ns:
                seq.trace.stage("engine.decode", seq.t_first_ns, end_ns,
                                tokens=seq.generated,
                                compiled=seq.decode_compiled)
            seq.trace.end(
                status="OK" if reason in (FINISH_STOP, FINISH_LENGTH)
                else "ERROR",
                finish_reason=reason, tokens=seq.generated)
        seq.finished = True
        if seq in self._running:
            self._running.remove(seq)
            self._lanes_ended += seq.prefilled
        if seq in self._waiting:
            self._waiting.remove(seq)
        self._give_slot(seq)
        if release_pages:
            if self._defer_releases is not None:
                # an in-flight speculative burst still writes these pages
                self._defer_releases.append(list(seq.pages))
            else:
                self.pool.release_sequence(seq.pages)
        seq.pages = []
        if self.tenant_metrics is not None and seq.tenant is not None:
            self.tenant_metrics.kv_blocks.set(
                self._tenant_pages(seq.tenant), tenant=seq.tenant)
        if emit:
            seq.queue.put_nowait(EngineOutput(
                token_ids=[], finish_reason=reason).to_dict())
        seq.queue.put_nowait(None)

    # -- disagg KV transfer (SURVEY §3.3; NIXL-replacement host path) -------

    async def read_kv_pages(self, page_ids: list[int]) -> np.ndarray:
        """Copy pages to host: (2, L, KVH, n, P, D) [k;v]. Takes the device
        lock — steps donate the cache buffers, so an unsynchronized read
        mid-step would touch a deleted array. The ICI device-to-device path
        replaces this for intra-pod transfers."""
        async with self._device_lock:
            return await asyncio.to_thread(self._read_kv_pages_sync, page_ids)

    def _gather_kv_pages(self, page_ids: list[int]):
        """The one gather: device-resident (2, L, KVH, n, P, D). Both the
        host and device transfer paths go through here so a cache-layout
        change can't skew them apart. ONE jitted program (not 2L+3
        eager ops): per-op dispatch dominated the transfer rate, and
        XLA fuses the per-layer gathers + stacks when it sees them
        together. Compile count is
        bounded by distinct page-group sizes (page-aligned transfer
        lengths)."""
        ids = jax.numpy.asarray(np.asarray(page_ids, dtype=np.int32))
        with self._kv_buffer_lock:
            trk = self.metrics.compile.track("gather_kv",
                                             (len(page_ids),))
            led = self.memory_ledger
            if led is not None:
                led.on_dispatch(trk.entry, trk.shape,
                                compiled=trk.compiled)
            with trk:
                out = self._mesh_dispatch(
                    trk, _gather_kv_jit, self.k_cache, self.v_cache,
                    ids)
                out.block_until_ready()
        rec = self.step_recorder
        if rec is not None:
            # timing/gap attribution only (no token work); the gather
            # stays device-resident, so block_until_ready is a lower
            # bound here, not the honest round-trip
            rec.record("gather_kv", trk.shape, trk.elapsed_s,
                       lanes=len(page_ids), compiled=trk.compiled,
                       synced=False)
        return out

    def _read_kv_pages_sync(self, page_ids: list[int]) -> np.ndarray:
        """Host copy — the wire/tier format."""
        return np.asarray(self._gather_kv_pages(page_ids))

    async def read_kv_pages_device(self, page_ids: list[int]):
        """Device-resident gather (2, L, KVH, n, P, D) — NO host copy.

        The ICI/device-to-device transfer path: the caller `device_put`s
        the result onto the destination engine's devices (same-process
        TPU→TPU rides DMA; the CPU mesh stands in for ICI in tests) and
        hands it to the decode request as ``kv_transfer_params.kv_data``
        — `write_kv_pages` accepts device arrays as-is, so the page bytes
        never touch host memory. Ref: SURVEY §7 step 7 (the NIXL analog,
        `block_manager/block/transfer/`)."""
        async with self._device_lock:
            return await asyncio.to_thread(self._gather_kv_pages, page_ids)

    def kv_import_sharding(self):
        """Sharding for a transfer array (2, L, KVH, n, P, D) matching
        this engine's cache layout — the device_put target for the ICI
        path (kv heads over "tp" when the engine runs on a mesh)."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = getattr(self.config, "mesh", None)
        if mesh is not None and "tp" in mesh.axis_names:
            return NamedSharding(
                mesh, PartitionSpec(None, None, "tp", None, None, None))
        return list(self.k_cache[0].devices())[0]

    def write_kv_pages(self, page_ids: list[int], data: np.ndarray) -> None:
        """Only call from within the scheduler's device-locked step (the
        prefill path does, for disagg imports). One jitted scatter —
        see _write_kv_pages_jit."""
        ids = jax.numpy.asarray(np.asarray(page_ids, dtype=np.int32))
        with self._kv_buffer_lock:
            trk = self.metrics.compile.track("write_kv",
                                             (len(page_ids),))
            led = self.memory_ledger
            if led is not None:
                led.on_dispatch(trk.entry, trk.shape,
                                compiled=trk.compiled)
            with trk:
                self.k_cache, self.v_cache = self._mesh_dispatch(
                    trk, _write_kv_pages_jit,
                    self.k_cache, self.v_cache, ids,
                    jax.numpy.asarray(data))
        rec = self.step_recorder
        if rec is not None:
            rec.record("write_kv", trk.shape, trk.elapsed_s,
                       lanes=len(page_ids), compiled=trk.compiled,
                       synced=False)

    def take_transfer(self, transfer_id: str) -> tuple[list[int], int]:
        """(pages, prefill_len) for a pinned transfer; KeyError if unknown
        or expired. Refreshes the TTL deadline: a chunked/device pull has
        many await points, and the reaper releasing (then a new prefill
        reusing) the pages mid-pull would stream the WRONG sequence's KV
        with no error. An abandoned pull still expires one ttl later."""
        pages, plen, _ = self._transfers[transfer_id]
        self._transfers[transfer_id] = (
            pages, plen, time.monotonic() + self.transfer_ttl)
        return pages, plen

    def complete_transfer(self, transfer_id: str) -> None:
        entry = self._transfers.pop(transfer_id, None)
        if entry is not None:
            self.pool.release_sequence(entry[0])

    def _reap_transfers(self) -> None:
        now = time.monotonic()
        for tid in [t for t, (_, _, dl) in self._transfers.items()
                    if dl <= now]:
            logger.warning("disagg transfer %s expired unpulled", tid)
            self.complete_transfer(tid)

    def _pick_victim(self, exclude: _Seq) -> Optional[_Seq]:
        cands = [s for s in self._running if s is not exclude and s.prefilled]
        if not cands:
            return None
        victim = max(cands, key=lambda s: s.arrival)
        self._preempt(victim)
        return victim

    def _preempt(self, seq: _Seq) -> None:
        """Release pages, fold generated tokens into the prompt, requeue at
        the head (re-prefill later; mocker/scheduler.rs preemption)."""
        if seq.trace is not None:
            seq.trace.event("preempted", generated=seq.generated)
        if seq in self._running:
            self._running.remove(seq)
        self.pool.release_sequence(seq.pages)
        seq.pages = []
        self._give_slot(seq)
        # block diffusion: what is committed plus the next block's known
        # head (there is no sampled-but-unwritten token)
        seq.prompt = seq.token_seq.tokens + (
            seq.given if self._dllm else [seq.next_token])
        seq.given = []
        seq.prompt_hashes = TokenBlockSequence(
            self.model_cfg.page_size, seq.prompt).seq_hashes()
        seq.token_seq = TokenBlockSequence(self.model_cfg.page_size)
        seq.cached_len = 0
        seq.prefill_pos = 0
        seq.prefilled = False
        self._waiting.insert(0, seq)

    def _publish_metrics(self) -> None:
        if self.metrics_sink is None:
            return
        perf = self.perf     # ONE derived snapshot of self.metrics
        sched_stats = {
            "prefill_chunks": perf["prefill_chunks"],
            "decode_steps_during_prefill":
                perf["decode_steps_during_prefill"],
            "mixed_steps": perf["mixed_steps"],
            "itl_p50_ms": itl_percentile(perf["itl_hist"], 0.5),
            "itl_p99_ms": itl_percentile(perf["itl_hist"], 0.99),
            "admission_stall_ms":
                round(perf["admission_stall_ms"], 3),
            "compiles": self.metrics.compile.total,
        }
        rec = self.step_recorder
        if rec is not None:
            # extra keys ONLY when the recorder is armed — the unset-
            # DYN_STEP_PROFILE payload stays byte-identical
            s = rec.summary()
            sched_stats["goodput_tokens"] = s["totals"]["good_tokens"]
            sched_stats["padded_tokens"] = s["totals"]["padded_tokens"]
            sched_stats["padded_pct"] = round(
                s["totals"]["padded_pct"], 3)
            sched_stats["dispatch_gap_mean_ms"] = round(
                s["dispatch_gap"]["mean_s"] * 1e3, 4)
        self.metrics_sink(ForwardPassMetrics(
            worker_id=self.config.worker_id, dp_rank=self.config.dp_rank,
            worker_stats=WorkerStats(
                request_active_slots=len(self._running),
                request_total_slots=self.config.max_batch_size,
                num_requests_waiting=len(self._waiting)),
            kv_stats=KvStats(
                kv_active_blocks=self.pool.active_pages,
                kv_total_blocks=self.pool.capacity,
                hbm_cache_usage=self.pool.usage()),
            spec_decode_stats=self._spec_stats,
            scheduler_stats=sched_stats,
        ))
