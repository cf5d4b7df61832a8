"""Embedder: host n-gram hashing (span ``embed.featurize``) per text
embedded, in ms."""
from bench import program


def read(ctx):
    s = program.spans(ctx).get("embed.featurize")
    if not s or not s["rows"]:
        return None
    return 1e3 * s["seconds"] / s["rows"]
