"""Seconds spent compiling or loading programs inside the window, as JAX's
``backend_compile_duration`` events report them: a compile of a shape no
warm-up met, or a load from the persistent cache of a program the program
lowered anew.  The window writes nothing to the cache, so a run pays what
an earlier run's window met again."""


def read(ctx):
    return float(sum(d for _, d in ctx.compiles))
