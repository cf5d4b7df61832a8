"""Service loop: mean requests per ``serve_batch`` call in the window
(the proxy's count of the rows of each call ``_serve_stdio`` makes)."""


def read(ctx):
    rows = ctx["child"]["batch_rows"]
    return sum(rows) / len(rows) if rows else None
