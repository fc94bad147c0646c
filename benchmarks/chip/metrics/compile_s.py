"""compile_s (s): backend compile seconds, cache loads included, from
JAX's monitoring events, up to the end of the window (none should fall
inside it)."""


def read(rec):
    return rec["compile_s"]
