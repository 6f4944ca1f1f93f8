"""Programs JAX lowered inside the measured window (each an in-memory
compile-cache miss; ``jax.monitoring`` events registered by the
benchmark).  The warm-up covers every shape it can enumerate; what is
left here compiles on the served path."""


def read(facts):
    return float(len(facts["compiles"]))
