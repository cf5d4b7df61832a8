"""Backend: host-clock time of one engine decode step, dispatch through
the sampled tokens' copy to the host (span ``engine.decode``), in ms."""
from bench import program


def read(ctx):
    s = program.spans(ctx).get("engine.decode")
    if not s or not s["calls"]:
        return None
    return 1e3 * s["seconds"] / s["calls"]
