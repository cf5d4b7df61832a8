"""Judge pool and promote: grey-zone submissions the pool dropped
(rate-limited, queue full or failed) over those submitted
(``PoolStats``, counted over the window)."""


def read(ctx):
    p = ctx["child"]["pool"]
    if not p.get("submitted"):
        return None
    return (p["rate_limited"] + p["dropped_full"] + p["failed"]) \
        / p["submitted"]
