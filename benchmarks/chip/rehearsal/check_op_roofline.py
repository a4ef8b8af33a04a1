"""Drives `readers/op_hbm_roofline.py` on a hand-made context: the share it
reads is the bytes of the forwards the trace counts over the peak, over the
matching operations' seconds; it reads nothing where the trace names no such
operation, no such program, or the family has no such byte count."""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import family                                   # noqa: E402


def reader():
    spec = importlib.util.spec_from_file_location(
        "op_hbm_roofline", os.path.join(HERE, "readers",
                                        "op_hbm_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main() -> int:
    read = reader()
    with open(os.path.join(HERE, "configs",
                           "sdar-30b-a3b-chat-int8.json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "layer_metrics",
                           "moe_gmm_hbm_roofline.json")) as f:
        args = json.load(f)["args"]
    fam = family.load("families", config)
    one = fam.moe_forward_bytes(config, 64 * 4)
    # 3 dispatches of 8 steps = 2 blocks x 5 forwards each: 30 forwards
    ctx = {"config": config, "peaks": {"hbm_bytes_per_s": 819e9},
           "span": {"lanes": 64.0},
           "trace": {"modules": {"jit_block_decode_multi_step":
                                 {"count": 3, "seconds": 0.4}},
                     "device_ops": [["moe_gmm", 0.3], ["fusion", 0.1]]}}
    want = 100.0 * 30 * one / 819e9 / 0.3
    got = read(ctx, **args)
    assert abs(got - want) < 1e-9 and 0 < got < 100, (got, want)
    # all 128 experts reached at 256 rows: the weights dominate
    assert 6.0e9 < one < 6.4e9, one
    for trace in ({"modules": {}, "device_ops": [["moe_gmm", 0.3]]},
                  {"modules": ctx["trace"]["modules"],
                   "device_ops": [["fusion", 0.1]]}):
        assert read({**ctx, "trace": trace}, **args) is None
    llama = {**config, "family": "llama"}
    assert read({**ctx, "config": llama}, **args) is None
    return 0


if __name__ == "__main__":
    sys.exit(main())
