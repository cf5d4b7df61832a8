"""Policy step: the self time of ``policy.serve_batch`` (the per-row
decision loop and all else outside its child spans: front, embed,
lookups, lock wait, backend, writes, grey submission, adaptation) per
call, in ms."""
from bench import program


def read(ctx):
    step = program.spans(ctx).get("policy.serve_batch")
    if not step or not step["calls"]:
        return None
    return 1e3 * step["self_seconds"] / step["calls"]
