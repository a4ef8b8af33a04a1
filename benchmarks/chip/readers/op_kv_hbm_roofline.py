"""Share of the memory roofline one kind of device operation reaches where
what it must move grows with the live context and not only with the rows:
the bytes the family says those operations must move in a forward
(`families/<family>.py:<bytes_fn>(config, kv_tokens, rows)`, at the tokens
of context the lanes held and the lanes' mean count during the traced span)
times the forwards the traced span ran (dispatches of the program `module`
x the steps one dispatch runs, a worker flag), over the chip's peak
bandwidth, over the device seconds of the trace's operations whose kind
matches `op`. `readers/op_hbm_roofline.py` passes rows only.

None where the trace names no such operation (a program from before it, a
path that fell back to XLA, or one of the ten largest kinds it is not), no
such program, or the family has no such byte count."""
import re

from lib import family
from lib.trace import program_time


def read(ctx, op, module, steps_flag, bytes_fn):
    seconds = sum(s for kind, s in ctx["trace"].get("device_ops", [])
                  if re.search(op, kind))
    count, _ = program_time(ctx["trace"], module)
    fam = family.load("families", ctx["config"])
    if not seconds or not count or not ctx["peaks"] \
            or not hasattr(fam, bytes_fn):
        return None
    flags = ctx["config"]["deployment"]["worker_flags"]
    forwards = count * int(flags[steps_flag])
    need = forwards * getattr(fam, bytes_fn)(
        ctx["config"], ctx["span"]["kv_tokens"], ctx["span"]["lanes"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
