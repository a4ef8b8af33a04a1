"""Check the trace reduction (`lib/trace.py`), without a chip.

1. Hand-made planes in the TPU's layout: union, nesting and gaps come out
   as computed by hand.
2. The recorded `small_tpu.xplane.pb` (12 dispatches of `jit_small_step` on
   a TPU v5 lite, see record_small_trace.py): 12 modules found, busy under
   the window, the loop's matmul among the top operations.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearsal/check_trace.py
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import trace  # noqa: E402


def check_by_hand() -> None:
    ops = [(0.0, 1.0, "%while.1 = while()"), (0.1, 0.4, "%fusion.7 = f()"),
           (0.5, 0.9, "%fusion.9 = f()"), (2.0, 3.0, "%copy.2 = copy()")]
    mods = [(0.0, 1.0, "jit_a(123)"), (2.0, 3.0, "jit_b(456)")]
    host = [(0.0, 4.0, "host thread")]
    out = trace.reduce_planes([
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}])
    assert out["window_s"] == 4.0 and out["busy_s"] == 2.0, out
    assert out["modules"] == {"jit_a": {"count": 1.0, "seconds": 1.0},
                              "jit_b": {"count": 1.0, "seconds": 1.0}}, out
    ops_self = dict(out["device_ops"])
    assert abs(ops_self["while"] - 0.3) < 1e-9, ops_self    # 1.0 - 0.3 - 0.4
    assert abs(ops_self["fusion"] - 0.7) < 1e-9, ops_self
    assert out["idle_gaps"] == [["before jit_b", 1.0]], out


def check_recorded() -> None:
    path = os.path.join(HERE, "small_tpu.xplane.pb")
    out = trace.reduce_planes(trace.load_planes(path))
    assert not out["stand_in"] and out["devices"] == 1, out
    step = out["modules"]["jit_small_step"]
    assert step["count"] == 12, step
    # a program's span holds its operations and the pauses between them
    assert 0 < out["busy_s"] <= step["seconds"] < 1.05 * out["busy_s"], out
    assert out["busy_s"] < out["window_s"], out
    assert out["device_ops"][0][0] in ("copy-done", "convolution_tanh_fusion")
    assert out["idle_gaps"][0][0] == "before jit_small_step", out
    print(f"recorded trace: window {out['window_s']:.4f}s busy "
          f"{out['busy_s']:.6f}s, 12 x jit_small_step "
          f"{step['seconds']:.6f}s, top op {out['device_ops'][0][0]}")


if __name__ == "__main__":
    check_by_hand()
    check_recorded()
    print("trace reduction: ok")
