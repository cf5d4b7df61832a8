"""Embedder: host-clock time in ``embed_batch_fn`` per row embedded."""


def read(ctx):
    s = ctx["child"]["spans"].get("embed")
    if not s or not s[2]:
        return None
    return 1e3 * s[1] / s[2]
