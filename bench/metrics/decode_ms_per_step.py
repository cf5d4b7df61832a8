"""Backend: host-clock time of one pipelined engine decode step (span
``engine.decode``), in ms: the dispatch of the next step, then the read
of this step's tokens, which waits until the device has produced them.
While the device is the bound, a span lasts about one device step."""
from bench import program


def read(ctx):
    s = program.spans(ctx).get("engine.decode")
    if not s or not s["calls"]:
        return None
    return 1e3 * s["seconds"] / s["calls"]
