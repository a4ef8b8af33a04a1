"""Share of the memory roofline a decode step reaches: the bytes it must
read (`families/<family>.py` of the cell's configuration, at the lanes' mean
count and context during the traced span) over the chip's peak bandwidth,
over the step's device time. Decode at these batch widths is memory-bound;
the compute bound is far below."""
from lib import family
from lib.trace import program_time


def read(ctx, module, steps_flag):
    count, seconds = program_time(ctx["trace"], module)
    if not count or not ctx["peaks"]:
        return None
    steps = int(ctx["config"]["deployment"]["worker_flags"][steps_flag])
    need = family.load("families", ctx["config"]).decode_step_bytes(
        ctx["config"], ctx["span"]["kv_tokens"], ctx["span"]["lanes"])
    least_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / (count * steps))
