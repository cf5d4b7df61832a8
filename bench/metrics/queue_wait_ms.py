"""Service loop: mean wait of a request from the service loop's read of
its line to the start of the ``serve_batch`` that serves it (the
program's counter ``loop.queue_wait_s``), in ms."""
from bench import program


def read(ctx):
    c = program.counters(ctx).get("loop.queue_wait_s")
    if not c or not c["n"]:
        return None
    return 1e3 * c["sum"] / c["n"]
