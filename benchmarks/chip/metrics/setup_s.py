"""setup_s (s): process start to the window's start: JAX's start, the
inputs, compiles or cache loads, and the warm-up."""


def read(rec):
    return rec["setup_s"]
