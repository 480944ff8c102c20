"""Device microseconds of the FES + stage-① executable per query."""

from bench import layers


def read(run):
    return layers.us_per_query(run, "jit_pilot_fn")
