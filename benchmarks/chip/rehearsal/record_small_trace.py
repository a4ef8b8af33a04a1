"""Record the small trace that `check_trace.py` reads: 12 dispatches of one
small jitted program (a matmul inside a 3-trip loop), profiled as the
worker's launcher profiles a window. Run where the trace should come from
(`chiprun -- python3 benchmarks/chip/rehearsal/record_small_trace.py <dir>`
for a TPU's planes) and keep the `.xplane.pb` it leaves as
`small_tpu.xplane.pb` beside this file."""

import sys
import time

import jax
import jax.numpy as jnp

DISPATCHES = 12


@jax.jit
def small_step(x):
    def body(_, acc):
        return jnp.tanh(acc @ acc)
    return jax.lax.fori_loop(0, 3, body, x)


if __name__ == "__main__":
    x = jnp.ones((256, 256), jnp.bfloat16)
    small_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
    for _ in range(DISPATCHES):
        small_step(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    print(jax.devices()[0].platform, jax.devices()[0].device_kind)
