"""Device milliseconds per step of one program: the device time of the
trace's modules whose name matches, over dispatches times the steps one
dispatch runs (a worker flag)."""
from lib.trace import program_time


def read(ctx, module, steps_flag=None):
    count, seconds = program_time(ctx["trace"], module)
    if not count:
        return None
    steps = 1
    if steps_flag:
        steps = int(ctx["config"]["deployment"]["worker_flags"][steps_flag])
    return 1e3 * seconds / (count * steps)
