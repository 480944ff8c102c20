"""Share of the bandwidth roofline the FES + stage-① executable reaches."""

from bench import layers


def read(run):
    return layers.roofline_pct(run, "jit_pilot_fn", layers.stage1_bytes)
