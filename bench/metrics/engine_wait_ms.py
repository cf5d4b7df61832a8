"""Backend: mean wait of a miss in the engine's batcher, from its submit
to the start of the engine batch that serves it (the program's counter
``batching-frontend.wait_s``), in ms."""
from bench import program


def read(ctx):
    c = program.counters(ctx).get("batching-frontend.wait_s")
    if not c or not c["n"]:
        return None
    return 1e3 * c["sum"] / c["n"]
