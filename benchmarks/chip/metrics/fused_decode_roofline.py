"""fused_decode_roofline: fused_decode's share of its roofline in the decode programs of
the traced window (device trace, counts from shapes)."""
from chipbench.kernels import roofline


def read(ctx):
    return roofline(ctx, "fused_decode")
