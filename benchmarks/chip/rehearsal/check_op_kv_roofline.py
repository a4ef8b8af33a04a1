"""Drives `readers/op_kv_hbm_roofline.py` on a hand-made context: an
operation present gives the share (the forwards the trace counts x the
family's bytes at the span's context, over the peak, over the operation's
seconds); absent, or with no such program, no peaks or a family without
the byte count, it gives None and does not raise. At the toy's sizes no
share above 100% can come out of the family's bytes: what the kernel must
move is less than what it moves in the time the chip's peak allows."""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import family                                   # noqa: E402


def reader():
    spec = importlib.util.spec_from_file_location(
        "op_kv_hbm_roofline", os.path.join(HERE, "readers",
                                           "op_kv_hbm_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load(*path):
    with open(os.path.join(HERE, *path)) as f:
        return json.load(f)


def main() -> int:
    read = reader()
    config = load("configs", "lfm2-8b-a1b-int8.json")
    args = load("layer_metrics", "attn_d64_hbm_roofline.json")["args"]
    fam = family.load("families", config)
    kv, lanes = 64 * 2400.0, 64.0
    one = fam.attn_forward_bytes(config, kv, lanes)
    # 12 288 B a token of context and the lanes' q and output, six layers
    assert one == kv * 12288 + 6 * lanes * 2 * 32 * 64 * 2, one
    # 5 dispatches of 8 steps: 40 forwards
    ctx = {"config": config, "peaks": {"hbm_bytes_per_s": 819e9},
           "span": {"lanes": lanes, "kv_tokens": kv},
           "trace": {"modules": {"jit_decode_multi_step":
                                 {"count": 5, "seconds": 0.9}},
                     "device_ops": [["moe_gmm", 0.5],
                                    ["paged_decode_attention", 0.16],
                                    ["fusion", 0.1]]}}
    want = 100.0 * 40 * one / 819e9 / 0.16
    got = read(ctx, **args)
    assert abs(got - want) < 1e-9 and 0 < got < 100, (got, want)
    for change in ({"trace": {"modules": {},
                              "device_ops": ctx["trace"]["device_ops"]}},
                   {"trace": {"modules": ctx["trace"]["modules"],
                              "device_ops": [["fusion", 0.1]]}},
                   {"trace": {"modules": ctx["trace"]["modules"]}},
                   {"peaks": {}},
                   {"config": {**config, "family": "llama"}},
                   {"config": load("configs",
                                   "nemotron-3-nano-30b-a3b-int8.json")}):
        assert read({**ctx, **change}, **args) is None, change
    # the toy: the kernel's seconds cannot be less than its bytes allow,
    # so the share stays under 100 at any context the toy can hold
    toy = load("rehearsal", "tiny-lfm2.json")
    for tokens in (16.0, 16 * 3584.0):
        need = fam.attn_forward_bytes(toy, tokens, 16.0)
        least_s = need / 819e9
        trace = {"modules": {"jit_decode_multi_step": {"count": 1,
                                                       "seconds": 1.0}},
                 "device_ops": [["paged_decode_attention", 8 * least_s]]}
        got = read({**ctx, "config": toy, "trace": trace,
                    "span": {"lanes": 16.0, "kv_tokens": tokens}}, **args)
        assert abs(got - 100.0) < 1e-6, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
