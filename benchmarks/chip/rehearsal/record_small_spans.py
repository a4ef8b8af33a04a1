"""Record the small trace that `check_host_spans.py` reads: the small step
of `record_small_trace.py`, each call under an `engine.dispatch` host span as
the engine's dispatch closures are (engine/profiler.py), then a stretch of
host work under `engine.emit`, a sleep between an `engine.wait.begin` /
`engine.wait.end` marker pair, and a short sleep under no span. Run where
the trace should come from
(`chiprun -- python3 benchmarks/chip/rehearsal/record_small_spans.py <dir>`)
and keep the `.xplane.pb` it leaves as `small_tpu_spans.xplane.pb` beside
this file."""

import sys
import time

import jax
from jax.profiler import TraceAnnotation

from record_small_trace import DISPATCHES, small_step

EMIT_S, WAIT_S, BARE_S = 0.001, 0.002, 0.0005


def busy_wait(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


if __name__ == "__main__":
    x = jax.numpy.ones((256, 256), jax.numpy.bfloat16)
    small_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
    for i in range(DISPATCHES):
        with TraceAnnotation("engine.dispatch", entry="small_step",
                             shape="256x256", tokens=i):
            small_step(x).block_until_ready()
        with TraceAnnotation("engine.emit"):
            busy_wait(EMIT_S)
        with TraceAnnotation("engine.wait.begin"):
            pass
        time.sleep(WAIT_S)
        with TraceAnnotation("engine.wait.end"):
            pass
        busy_wait(BARE_S)
    jax.profiler.stop_trace()
    print(jax.devices()[0].platform, jax.devices()[0].device_kind)
