"""Model zoo for the TPU engine (we own the engine; the reference delegates
to vLLM/SGLang/TRT-LLM — SURVEY.md §7 step 5)."""

from dynamo_tpu.models.llama import LlamaConfig, init_params

__all__ = ["LlamaConfig", "init_params", "family_module"]


def family_module(cfg):
    """The module that serves a configuration: its `init_params`,
    `init_cache`, `prefill_batch` and `decode_multi_step` (the engine's
    entries) and, for a family that is a file of its own, its checkpoint's
    layout (`load_params`). A configuration's class names such a family
    (`entries_module`: models/nemotron_h.py, whose layers are not the
    two-halves block); it is imported when such a model is served and not
    before. Every other configuration is models/llama.py's."""
    import importlib

    return importlib.import_module(
        getattr(cfg, "entries_module", "dynamo_tpu.models.llama"))
