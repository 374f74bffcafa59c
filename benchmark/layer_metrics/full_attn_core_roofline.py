"""The full layers' attention cores' share of their roofline: the least
time the chip could take for one step's `q k^T` and `p v` over the causal
half (134,225,920 pairs a head at 16,384 positions), forward and backward,
the `full_attention` layers (benchmark/shapes/afmoe.py:
attention_core_passes) over the time measured in them
(`full_attn_core_device_ms`)."""

from benchmark import afmoe_scopes


def read(run):
    return afmoe_scopes.core_roofline(run, afmoe_scopes.FULL)
