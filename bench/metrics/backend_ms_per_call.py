"""Backend: host-clock time per ``backend_batch_fn`` call in the
window (one call carries a serve batch's misses, in engine batches of
eight)."""


def read(ctx):
    s = ctx["child"]["spans"].get("backend")
    if not s or not s[0]:
        return None
    return 1e3 * s[1] / s[0]
