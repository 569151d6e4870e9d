"""Share of the window's wall time spent in JAX's tracing, lowering and
compiling (``jax.monitoring`` time spans, their union), in percent."""


def read(ctx):
    return 100.0 * ctx["compile_share"]
