"""The window layers' attention cores' share of their roofline: the least
time the chip could take for one step's `q k^T` and `p v` over the pairs
the window ALLOWS (query i sees `min(i + 1, 2,048)` keys: 31,458,304 a
head at 16,384 positions), forward and backward, the `sliding_attention`
layers (benchmark/shapes/afmoe.py:attention_core_passes — per pass the
larger of operations over the peak bf16 FLOP/s and least bytes over the
peak HBM bytes/s) over the time measured in them
(`win_attn_core_device_ms`). What a kernel computes beyond the allowed
pairs (the rest of the band's edge tiles) is time, not work: see
`win_attn_pairs_computed_ratio`."""

from benchmark import afmoe_scopes


def read(run):
    return afmoe_scopes.core_roofline(run, afmoe_scopes.SLIDING)
