"""setup_compile_s (s): seconds under JAX's backend-compile event during
set-up (a persistent-cache hit counts its load time)."""


def read(run):
    return run.setup_compile_s
