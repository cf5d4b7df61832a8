"""Lookup kernels: the least time the traced lookups could take from
the work the configuration's lookup semantics require
(``bench/work.py``) and the chip's peaks, over the device time of the
lookup programs in the trace (the configuration's
``lookup_programs``), in percent. The profiler keeps a bounded number
of events, so the trace may end before the window does: the work
counted is that of the window's first batches, as many as the trace
holds calls of every lookup program."""
from bench import trace, work


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    names = ctx["deployment"]["lookup_programs"]
    dev_s, calls = trace.program_seconds(red, names)
    lk = dict(ctx["child"]["lookup"])
    n = calls // len(names)
    if dev_s <= 0 or n <= 0:
        return None
    lk["batches"] = lk["batches"][:n]
    ops, byts = work.lookup(lk)
    return 100.0 * work.roofline_s(ops, byts, ctx["peaks"]) / dev_s
