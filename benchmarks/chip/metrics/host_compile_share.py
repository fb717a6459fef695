"""Share of the window the host spent tracing, lowering and compiling
or loading from the persistent cache (``jax.monitoring``): the entry's
own host path, since ``simulate`` builds a new ``jax.jit`` per call."""

UNIT = "fraction"


def read(run):
    if not run.jobs or run.window_s <= 0:
        return None
    return run.compile["seconds"] / run.window_s
