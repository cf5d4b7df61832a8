"""Policy step: the operations the window's batches required (embedder
MLP and lookups, ``bench/work.py``; the backend, from the
configuration's reference module's ``backend_ops``) over the summed
``serve_batch`` wall time at the chip's bfloat16 peak, in percent."""
from bench import work


def read(ctx):
    ch, dep = ctx["child"], ctx["deployment"]
    span = ch["spans"].get("serve_batch")
    if not span or span[1] <= 0:
        return None
    embedded = ch["spans"].get("embed", [0, 0.0, 0])[2]
    ops = work.embed(embedded, d=int(dep["embedding_dim"])) \
        + work.lookup(ch["lookup"])[0] \
        + ctx["reference"].backend_ops(dep["backend"], ch["backend_work"])
    return 100.0 * ops / (span[1] * ctx["peaks"]["bf16_flops_per_s"])
