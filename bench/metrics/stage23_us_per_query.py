"""Device microseconds of the stage-② and -③ executable per query."""

from bench import layers


def read(run):
    return layers.us_per_query(run, "jit_cpu_fn")
