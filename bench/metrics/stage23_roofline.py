"""Share of the bandwidth roofline the stage-② and -③ executable
reaches."""

from bench import layers


def read(run):
    return layers.roofline_pct(run, "jit_cpu_fn", layers.stage23_bytes)
