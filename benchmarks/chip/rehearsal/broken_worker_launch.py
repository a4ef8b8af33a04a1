"""`lib/worker_launch.py` with the timed path broken underneath: every
sampled token whose id is a multiple of four is altered where it is
produced. The stream keeps its shape, the probe repeats, nothing compiles
in the window: only the comparison with the reference can tell.
`check_reference.py` starts the worker through this and sees `correct`
come out false."""

import os
import runpy
import sys

import jax.numpy as jnp

from dynamo_tpu.engine import sampling

_sound = sampling.sample_tokens_traced


def _altered(logits, *args, **kwargs):
    tokens = _sound(logits, *args, **kwargs)
    return jnp.where(tokens % 4 == 0, (tokens + 1) % logits.shape[-1],
                     tokens)


sampling.sample_tokens_traced = _altered
launch = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib", "worker_launch.py")
sys.argv[0] = launch
runpy.run_path(launch, run_name="__main__")
