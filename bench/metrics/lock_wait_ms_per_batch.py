"""Policy step: time a serve batch waits to take the dynamic tier's lock
(span ``policy.lock_wait``) per ``policy.serve_batch`` call, in ms."""
from bench import program


def read(ctx):
    s = program.spans(ctx)
    wait, step = s.get("policy.lock_wait"), s.get("policy.serve_batch")
    if not wait or not step or not step["calls"]:
        return None
    return 1e3 * wait["seconds"] / step["calls"]
