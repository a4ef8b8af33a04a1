"""Share of the memory roofline one kind of device operation reaches: the
bytes the family says those operations must move in a forward
(`families/<family>.py:<bytes_fn>(config, rows)`, rows = the lanes' mean
count during the traced span x the block length) times the forwards the
traced span ran, over the chip's peak bandwidth, over the device seconds of
the trace's operations whose kind matches `op`.

The forwards are counted from the trace itself: dispatches of the program
`module` x the forwards one dispatch runs, (decode-steps-per-sync / B)
blocks of T denoising forwards and one that commits. Operations of that
kind outside `module` (a prefill round's grouped products) add their time
and none of their bytes, so the share reads a little low, never above what
the chip did. None where the trace names no such operation (a program from
before it, or one of the ten largest kinds it is not)."""
import re

from lib import family
from lib.trace import program_time


def read(ctx, op, module, bytes_fn):
    seconds = sum(s for kind, s in ctx["trace"].get("device_ops", [])
                  if re.search(op, kind))
    count, _ = program_time(ctx["trace"], module)
    fam = family.load("families", ctx["config"])
    if not seconds or not count or not ctx["peaks"] \
            or not hasattr(fam, bytes_fn):
        return None
    flags = ctx["config"]["deployment"]["worker_flags"]
    block, steps = fam.block_steps(ctx["config"])
    forwards = count * int(flags["decode-steps-per-sync"]) // block \
        * (steps + 1)
    need = forwards * getattr(fam, bytes_fn)(
        ctx["config"], ctx["span"]["lanes"] * block)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
