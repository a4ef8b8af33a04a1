"""Checkpoint loading: HF-style safetensors → the engine's param pytree.

Reference: `lib/llm/src/local_model.rs:449` (LocalModel resolution) and
`lib/llm/src/hub.rs` (HF-hub cache lookup). Zero-egress environment: we
resolve local directories and already-downloaded HF cache snapshots — no
network fetch path.

Layout mapping (HF `LlamaForCausalLM` → models/llama.py init_params):

  model.embed_tokens.weight            (V, E)      → embed       (V, E)
  .layers.{i}.self_attn.q_proj.weight  (H·D, E)    → wq[i]       (E, H·D)ᵀ
  .layers.{i}.self_attn.k_proj.weight  (KVH·D, E)  → wk[i]       (E, KVH·D)ᵀ
  .layers.{i}.self_attn.v_proj.weight  (KVH·D, E)  → wv[i]       (E, KVH·D)ᵀ
  .layers.{i}.self_attn.o_proj.weight  (E, H·D)    → wo[i]       (H·D, E)ᵀ
  .layers.{i}.mlp.gate_proj.weight     (F, E)      → w_gate[i]   (E, F)ᵀ
  .layers.{i}.mlp.up_proj.weight       (F, E)      → w_up[i]     (E, F)ᵀ
  .layers.{i}.mlp.down_proj.weight     (E, F)      → w_down[i]   (F, E)ᵀ
  .layers.{i}.input_layernorm.weight   (E,)        → attn_norm[i] (fp32)
  .layers.{i}.post_attention_layernorm (E,)        → mlp_norm[i]  (fp32)
  model.norm.weight                    (E,)        → final_norm   (fp32)
  lm_head.weight                       (V, E)      → lm_head     (E, V)ᵀ
                                       (tied ⇒ embedᵀ)

RoPE: transformers checkpoints use the rotate-half convention (q/k weights
already permuted from Meta's interleaved layout), which is exactly what
models/llama.py `rope` computes — weights load without re-permutation.
"""

from __future__ import annotations

import glob
import json
import logging
import os
from typing import Any, Optional

import numpy as np

from dynamo_tpu.models import family_module
from dynamo_tpu.models.llama import LlamaConfig

logger = logging.getLogger(__name__)


# Mixtral FFN key mapping: ours -> HF block_sparse_moe expert tensor.
# ONE definition: the host loader's expert stacking, the device
# loader's prefetch ORDER, and the device body's consumption all read
# this — the prefetcher contract (reads replay the order exactly)
# breaks if any copy drifts.
MOE_FFN = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
# The Qwen3-MoE layout (qwen3_moe, sdar_moe): the router is `mlp.gate`, an
# expert's projections carry the dense MLP's names.
QWEN3_MOE_FFN = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                 ("w_down", "down_proj"))


def _moe_layout(idx) -> tuple[str, tuple]:
    """(prefix under `model.layers.{}.` of the router and the experts, the
    FFN key mapping) of the MoE layout this checkpoint is written in."""
    if "model.layers.0.mlp.gate.weight" in idx:
        return "mlp.", QWEN3_MOE_FFN
    return "block_sparse_moe.", MOE_FFN


def resolve_model(name_or_path: str) -> str:
    """Local dir, or an HF-cache snapshot for `org/name` (hub.rs:~).

    Raises FileNotFoundError with the looked-up locations otherwise.
    """
    if os.path.isdir(name_or_path):
        return name_or_path
    cache_root = os.environ.get(
        "HF_HOME", os.path.expanduser("~/.cache/huggingface"))
    repo_dir = os.path.join(
        cache_root, "hub", "models--" + name_or_path.replace("/", "--"))
    snapshots = sorted(
        glob.glob(os.path.join(repo_dir, "snapshots", "*")),
        key=os.path.getmtime, reverse=True)
    for snap in snapshots:
        if glob.glob(os.path.join(snap, "*.safetensors")) or \
                os.path.exists(os.path.join(snap, "config.json")):
            return snap
    raise FileNotFoundError(
        f"model '{name_or_path}' is neither a directory nor a cached HF "
        f"snapshot (looked in {repo_dir}; this environment cannot download)")


def config_from_hf(path: str, **overrides: Any) -> LlamaConfig:
    """LlamaConfig (or MoeConfig for Mixtral-family checkpoints) from a
    checkpoint dir's config.json."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    if hf.get("model_type") == "nemotron_h":
        # a hybrid stack (Mamba-2, expert and attention layers): a family
        # as a file of its own, config, entries and checkpoint layout
        from dynamo_tpu.models import nemotron_h

        return nemotron_h.config_from_hf(hf, **overrides)
    if hf.get("model_type") == "lfm2_moe":
        # gated short convolutions and attention, dense then expert FFNs:
        # the same kind of family
        from dynamo_tpu.models import lfm2_moe

        return lfm2_moe.config_from_hf(hf, **overrides)
    arch = (hf.get("architectures") or ["LlamaForCausalLM"])[0]
    # every layer of the families below is attention + FFN: a stack that
    # names another kind of layer would load with tensors missing, or not
    # fail at all
    other = sorted(set(hf.get("layer_types") or ()) - {"full_attention"})
    if other:
        raise ValueError(
            f"{arch} checkpoint at {path} (model_type "
            f"{hf.get('model_type')!r}): layer_types names {other}, which "
            "the llama-family loader does not serve; a stack of mixed "
            "layers is a family of its own (models/nemotron_h.py, "
            "models/lfm2_moe.py)")
    known = ("llama", "mistral", "mixtral", "qwen2", "qwen3moe", "sdar")
    if not any(f in arch.lower() for f in known):
        logger.warning("loading %s with the llama-family loader", arch)
    hidden = hf["hidden_size"]
    heads = hf["num_attention_heads"]
    cfg = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hidden,
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hidden // heads,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=float(hf.get("rms_norm_eps", 1e-5)),
        # Qwen2 attention carries q/k/v biases architecturally (its
        # config.json has no attention_bias key); llama3-style configs
        # state it explicitly
        attention_bias=bool(hf.get("attention_bias",
                                   "qwen2" in arch.lower())),
    )
    cls = LlamaConfig
    if hf.get("model_type") in ("qwen3_moe", "sdar_moe"):
        # the Qwen3-MoE block: per-head q/k norm, every layer sparse, the
        # top-k gates renormalised; SDAR generates by diffusion over blocks
        # on top of it and names its mask id
        from dynamo_tpu.models.mixtral import MoeConfig

        if (hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers")
                or not hf.get("norm_topk_prob", True)
                or hf.get("shared_expert_intermediate_size")):
            raise ValueError(
                f"{hf['model_type']} checkpoint at {path}: only the layout "
                "with every layer sparse, no shared expert and "
                "norm_topk_prob is served")
        cls = MoeConfig
        cfg.update(num_experts=int(hf["num_experts"]),
                   experts_per_token=int(hf["num_experts_per_tok"]),
                   intermediate_size=int(hf["moe_intermediate_size"]),
                   qk_norm=True,
                   mask_token_id=int(hf.get("mask_token_id", -1)))
    elif "mixtral" in arch.lower() or hf.get("num_local_experts"):
        from dynamo_tpu.models.mixtral import MoeConfig

        n_exp = hf.get("num_local_experts")
        if not n_exp:
            raise ValueError(
                f"{arch} checkpoint at {path} has no num_local_experts "
                f"in config.json — cannot size the expert stacks")
        cls = MoeConfig
        cfg["num_experts"] = int(n_exp)
        cfg["experts_per_token"] = int(hf.get("num_experts_per_tok", 2))
    cfg.update(overrides)
    return cls(**cfg)


class _TensorIndex:
    """name → numpy array across one or many .safetensors shards."""

    def __init__(self, path: str) -> None:
        from safetensors import safe_open

        self._safe_open = safe_open
        self.path = path
        index_path = os.path.join(path, "model.safetensors.index.json")
        if os.path.exists(index_path):
            with open(index_path) as f:
                self._map = json.load(f)["weight_map"]
        else:
            files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
            if not files:
                raise FileNotFoundError(f"no .safetensors under {path}")
            self._map = {}
            for fp in files:
                with safe_open(fp, framework="np") as f:
                    for name in f.keys():
                        self._map[name] = os.path.basename(fp)
        self._handles: dict[str, Any] = {}

    def __contains__(self, name: str) -> bool:
        return name in self._map

    def get(self, name: str) -> np.ndarray:
        fname = self._map[name]
        h = self._handles.get(fname)
        if h is None:
            h = self._safe_open(os.path.join(self.path, fname),
                                framework="np")
            self._handles[fname] = h
        t = h.get_tensor(name)
        if t.dtype.kind == "V":  # bfloat16 loads as void through numpy
            import ml_dtypes

            t = t.view(ml_dtypes.bfloat16)
        return t

    def close(self) -> None:
        self._handles.clear()


def load_llama_params(path: str, cfg: LlamaConfig) -> dict:
    """Host-numpy param pytree in init_params' layout. Dense weights are
    cast to cfg.dtype, norms to fp32 (matching init_params)."""
    import ml_dtypes

    if getattr(cfg, "entries_module", None):
        return family_module(cfg).load_params(path, cfg)
    w_dtype = np.dtype(ml_dtypes.bfloat16) \
        if cfg.dtype.__name__ == "bfloat16" else np.dtype(cfg.dtype.__name__)
    idx = _TensorIndex(path)
    L = cfg.num_layers

    def dense(name: str, transpose: bool = True) -> np.ndarray:
        t = idx.get(name)
        if transpose:
            t = t.T
        return np.ascontiguousarray(t).astype(w_dtype)

    def stack(fmt: str) -> np.ndarray:
        return np.stack([dense(fmt.format(i)) for i in range(L)])

    def stack_norm(fmt: str) -> np.ndarray:
        return np.stack([idx.get(fmt.format(i)).astype(np.float32)
                         for i in range(L)])

    p = "model.layers.{}."
    moe = bool(getattr(cfg, "num_experts", 0))
    layers = {
        "attn_norm": stack_norm(p + "input_layernorm.weight"),
        "wq": stack(p + "self_attn.q_proj.weight"),
        "wk": stack(p + "self_attn.k_proj.weight"),
        "wv": stack(p + "self_attn.v_proj.weight"),
        "wo": stack(p + "self_attn.o_proj.weight"),
        "mlp_norm": stack_norm(p + "post_attention_layernorm.weight"),
    }
    if moe:
        # Mixtral layout: block_sparse_moe.gate (router) + per-expert
        # w1 (gate) / w3 (up) / w2 (down), stacked to the (L, X, ...)
        # expert stacks mixtral.init_moe_params defines
        X = cfg.num_experts
        prefix, ffn = _moe_layout(idx)
        bs = p + prefix

        def stack_experts(w_fmt: str) -> np.ndarray:
            return np.stack([
                np.stack([dense(bs.format(i) + w_fmt.format(e))
                          for e in range(X)]) for i in range(L)])

        layers["router"] = stack(bs + "gate.weight")
        for key, w in ffn:
            layers[key] = stack_experts(
                "experts.{}." + w + ".weight")
    else:
        layers["w_gate"] = stack(p + "mlp.gate_proj.weight")
        layers["w_up"] = stack(p + "mlp.up_proj.weight")
        layers["w_down"] = stack(p + "mlp.down_proj.weight")
    params = {
        "embed": dense("model.embed_tokens.weight", transpose=False),
        "layers": layers,
        "final_norm": idx.get("model.norm.weight").astype(np.float32),
    }
    if cfg.attention_bias:
        # Qwen2 family: q/k/v carry additive biases (1-D, no transpose)
        for key, name in (("bq", "q_proj"), ("bk", "k_proj"),
                          ("bv", "v_proj")):
            params["layers"][key] = np.stack(
                [idx.get(p.format(i) + f"self_attn.{name}.bias")
                 .astype(w_dtype) for i in range(L)])
    if cfg.qk_norm:
        for key in ("q_norm", "k_norm"):
            params["layers"][key] = stack_norm(
                p + f"self_attn.{key}.weight")
    if "lm_head.weight" in idx:
        params["lm_head"] = dense("lm_head.weight")
    else:  # tie_word_embeddings
        params["lm_head"] = np.ascontiguousarray(params["embed"].T)
    idx.close()
    return params


class _Prefetcher:
    """Reads tensors ONE thread ahead of the consumer so disk I/O
    overlaps the previous tensor's device upload + on-chip prep. The
    consumer must request names in exactly the order given (asserted).
    Bounded queue: at most `depth` raw tensors buffered on host.

    stop() unblocks the reader even when the consumer abandoned the
    load mid-way (a device OOM in the prep loop must not leave a
    thread parked forever on the full queue, pinning shard handles)."""

    def __init__(self, idx: "_TensorIndex", ordered_names: list,
                 depth: int = 2) -> None:
        import queue
        import threading

        self._q: Any = queue.Queue(maxsize=depth)
        self._queue_mod = queue
        self._stop = threading.Event()

        def run():
            try:
                for name in ordered_names:
                    item = (name, idx.get(name), None)
                    while not self._stop.is_set():
                        try:
                            self._q.put(item, timeout=0.5)
                            break
                        except queue.Full:
                            continue
                    if self._stop.is_set():
                        return
            except BaseException as e:   # surface in the consumer
                try:
                    self._q.put((None, None, e), timeout=5)
                except queue.Full:
                    pass

        self._t = threading.Thread(target=run, daemon=True)
        self._t.start()

    def get(self, name: str) -> np.ndarray:
        got, arr, err = self._q.get()
        if err is not None:
            raise err
        assert got == name, f"prefetch order broke: {got} != {name}"
        return arr

    def stop(self) -> None:
        self._stop.set()
        # drain one slot so a put-blocked reader can observe the stop
        try:
            self._q.get_nowait()
        except self._queue_mod.Empty:
            pass
        self._t.join(timeout=60)


def load_llama_params_device(path: str, cfg: LlamaConfig,
                             quantize=False) -> dict:
    """Checkpoint → DEVICE param pytree, transposing/casting/quantizing
    on the accelerator.

    Why not load_llama_params + placement: HF stores dense weights
    (out, in); the host-side `.T` + contiguous copy over a 16 GB
    checkpoint takes tens of minutes on a small host (strided bf16
    copies), and a big model's bf16 can't be device-resident all at
    once anyway (Llama-3-8B bf16 = 16 GB = a whole v5e). Here each raw
    tensor is uploaded as stored, and transpose + cast (+ int8
    quantization, keeping only the int8 on device) run on the chip;
    per-layer results are stacked device-side.

    Load-time shape:
    - disk reads run on a PREFETCH thread, overlapping each tensor's
      read with the previous one's upload/prep;
    - the per-tensor block_until_ready (× ~300 tensors on an 8B)
      becomes one sync every _SYNC_EVERY
      tensors — single-stream TPU execution completes ops in dispatch
      order, so syncing the newest bounds ALL outstanding transients.
    Peak HBM ≈ final params + _SYNC_EVERY tensors' transients
    (~1 GB at 8B scale)."""
    import functools

    import jax
    import jax.numpy as jnp

    if getattr(cfg, "entries_module", None):
        return family_module(cfg).load_params(path, cfg,
                                              quantize=quantize)

    from dynamo_tpu.engine.quant import (
        QUANT_KEYS,
        _act_bits_of,
        _bits_of,
        quantize as quant_fn,
    )

    bits = _bits_of(quantize)      # falsy | "int8" | "w8a8" | "int4"
    act_bits = _act_bits_of(quantize)

    moe = bool(getattr(cfg, "num_experts", 0))
    if moe and quantize and quantize != "int8":
        raise ValueError(
            "MoE expert stacks support weight-only int8 only "
            "(w8a8/int4 expert kernels don't exist yet)")

    idx = _TensorIndex(path)
    L = cfg.num_layers

    @jax.jit
    def prep_t(w):                      # (out, in) -> (in, out) cast
        return jnp.transpose(w).astype(cfg.dtype)

    @jax.jit
    def prep(w):                        # cast only
        return w.astype(cfg.dtype)

    p = "model.layers.{}."
    names = {
        "wq": p + "self_attn.q_proj.weight",
        "wk": p + "self_attn.k_proj.weight",
        "wv": p + "self_attn.v_proj.weight",
        "wo": p + "self_attn.o_proj.weight",
    }
    if not moe:
        names.update({
            "w_gate": p + "mlp.gate_proj.weight",
            "w_up": p + "mlp.up_proj.weight",
            "w_down": p + "mlp.down_proj.weight",
        })
    # Mixtral FFN: router + per-expert tensors, streamed one tensor at
    # a time like everything else (a host-side expert-stack build of an
    # 8x7B would need ~2x checkpoint RAM and tens of minutes of strided
    # transposes — exactly what this function exists to avoid)
    prefix, ffn = _moe_layout(idx)
    bs = p + prefix

    # exact read order (the prefetcher replays it; EVERY read goes
    # through it — the safetensors handles must only be touched by the
    # reader thread)
    order = [fmt.format(i) for fmt in names.values() for i in range(L)]
    if moe:
        order += [bs.format(i) + "gate.weight" for i in range(L)]
        for _, w in ffn:
            order += [bs.format(i) + f"experts.{e}.{w}.weight"
                      for i in range(L)
                      for e in range(cfg.num_experts)]
    norms = {"attn_norm": "input_layernorm.weight",
             "mlp_norm": "post_attention_layernorm.weight"}
    if cfg.qk_norm:
        norms.update(q_norm="self_attn.q_norm.weight",
                     k_norm="self_attn.k_norm.weight")
    for fmt in norms.values():
        order += [p.format(i) + fmt for i in range(L)]
    if cfg.attention_bias:
        for name in ("q_proj", "k_proj", "v_proj"):
            order += [p.format(i) + f"self_attn.{name}.bias"
                      for i in range(L)]
    order.append("model.embed_tokens.weight")
    order.append("model.norm.weight")
    if "lm_head.weight" in idx:
        order.append("lm_head.weight")
    pf = _Prefetcher(idx, order)

    _SYNC_EVERY = 8
    state = {"n": 0, "last": None}

    def throttle(out):
        """Bound in-flight transients without a sync per tensor."""
        state["last"] = out
        state["n"] += 1
        if state["n"] >= _SYNC_EVERY:
            out.block_until_ready()
            state["n"] = 0
        return out

    def dense(name, transpose=True):
        t = jax.device_put(pf.get(name))
        return throttle(prep_t(t) if transpose else prep(t))

    q_layer = jax.jit(functools.partial(quant_fn, bits=bits,
                                        act_bits=act_bits),
                      donate_argnums=(0,))
    import logging

    _log = logging.getLogger(__name__)
    try:
        return _load_device_body(
            cfg, idx, pf, names, p, dense, throttle, state, q_layer,
            quantize, quant_fn, bits, act_bits, L, _log, bs, ffn, norms)
    finally:
        # unblock + join the reader even when the prep loop raised
        # (device OOM mid-load must not leak a put-blocked thread
        # pinning shard handles)
        pf.stop()
        idx.close()


def _load_device_body(cfg, idx, pf, names, p, dense, throttle, state,
                      q_layer, quantize, quant_fn, bits, act_bits, L,
                      _log, bs, ffn, norms) -> dict:
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.quant import QUANT_KEYS, QTensor

    def q_stack(name_tree):
        """Quantize each named tensor then stack following the nesting
        (a list of names → one stack axis; nested lists → nested
        axes) — THE quantize-before-stack recipe shared by the dense
        (L,) and expert (L, X) paths, so transients stay int8
        (stacking 32 bf16 layers first would spike peak HBM past a
        16 GB chip near the end of an 8B load)."""
        def rec(node):
            if isinstance(node, str):
                qt = q_layer(dense(node))
                throttle(qt.q)
                return qt.q, qt.s
            pairs = [rec(child) for child in node]
            return (jnp.stack([a for a, _ in pairs]),
                    jnp.stack([b for _, b in pairs]))

        q, s = rec(name_tree)
        return QTensor(q=q, s=s, bits=bits, act_bits=act_bits)

    layers: dict[str, Any] = {}
    for key, fmt in names.items():
        _log.info("loading %s (%d layers)", key, L)
        if quantize and key in QUANT_KEYS:
            layers[key] = q_stack([fmt.format(i) for i in range(L)])
        else:
            layers[key] = jnp.stack(
                [dense(fmt.format(i)) for i in range(L)])
    if getattr(cfg, "num_experts", 0):
        X = cfg.num_experts
        _log.info("loading MoE router + %d experts x %d layers", X, L)
        layers["router"] = jnp.stack(
            [dense(bs.format(i) + "gate.weight") for i in range(L)])
        for key, w in ffn:
            if quantize:
                # per-(layer,expert) scales == quantizing the full
                # stack: the reduction is over the contraction dim only
                layers[key] = q_stack(
                    [[bs.format(i) + f"experts.{e}.{w}.weight"
                      for e in range(X)] for i in range(L)])
            else:
                layers[key] = jnp.stack([
                    jnp.stack([dense(bs.format(i)
                                     + f"experts.{e}.{w}.weight")
                               for e in range(X)]) for i in range(L)])
    for key, fmt in norms.items():
        layers[key] = jnp.stack(
            [jnp.asarray(pf.get(p.format(i) + fmt), dtype=jnp.float32)
             for i in range(L)])
    if cfg.attention_bias:
        # Qwen2 family: 1-D q/k/v biases (tiny — host stack is fine)
        for key, name in (("bq", "q_proj"), ("bk", "k_proj"),
                          ("bv", "v_proj")):
            layers[key] = jnp.stack(
                [jnp.asarray(pf.get(p.format(i) + f"self_attn.{name}"
                                    f".bias"), dtype=cfg.dtype)
                 for i in range(L)])
    params: dict[str, Any] = {
        "embed": dense("model.embed_tokens.weight", transpose=False),
        "layers": layers,
        "final_norm": jnp.asarray(pf.get("model.norm.weight"),
                                  dtype=jnp.float32),
    }
    _log.info("loading embed/lm_head")
    if "lm_head.weight" in idx:
        lm = dense("lm_head.weight")
    else:
        # tie_word_embeddings: the (E, V) copy is materialized — true
        # weight sharing would need a transposed-matmul marker through
        # qm(); at 128k vocab bf16 that is ~1 GB of avoidable HBM, an
        # accepted cost until a tied checkpoint at that scale matters
        # (the int8 path quantizes the copy and frees it)
        lm = jnp.transpose(params["embed"])
    from dynamo_tpu.engine.quant import _lm_head_quant_ok

    if quantize and _lm_head_quant_ok(lm):
        # lm_head stays int8 even under int4 (logit quality)
        qt = jax.jit(quant_fn, donate_argnums=(0,))(lm)
        qt.q.block_until_ready()
        params["lm_head"] = qt
        if state["last"] is lm:
            # lm was DONATED to the quant jit — the drain below must
            # never touch the deleted buffer (TPU honors donation;
            # CPU tests don't, so only a real chip would crash)
            state["last"] = qt.q
    else:
        # big-vocab lm_head stays bf16: the int8 (E, 128k) matmul sends
        # XLA/Mosaic compile into a tailspin (quant.py
        # LM_HEAD_QUANT_MAX_VOCAB)
        params["lm_head"] = lm
    # drain outstanding dispatches before handing the pytree out (the
    # throttle only syncs every _SYNC_EVERY tensors)
    if state["last"] is not None:
        state["last"].block_until_ready()
    _log.info("post-load device footprint: %.1f MiB",
              params_footprint(params) / 2 ** 20)
    return params


def params_footprint(params) -> int:
    """Resident bytes of a (possibly quantized) param pytree — the
    number the memory ledger books as the ``weights`` class. QTensor
    leaves flatten to their q/s arrays under jax.tree, so int8/int4
    footprints come out right without special-casing."""
    try:
        import jax

        return int(sum(
            int(getattr(x, "nbytes", 0) or 0)
            for x in jax.tree.leaves(params)))
    except Exception:
        return 0


def load_model(name_or_path: str, **cfg_overrides: Any
               ) -> tuple[LlamaConfig, dict]:
    """(config, host params) for a local/cached checkpoint."""
    path = resolve_model(name_or_path)
    cfg = config_from_hf(path, **cfg_overrides)
    return cfg, load_llama_params(path, cfg)
