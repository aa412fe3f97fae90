"""qmatmul_w8a8_roofline: qmatmul_w8a8's share of its roofline in the decode programs of
the traced window (device trace, counts from shapes)."""
from chipbench.kernels import roofline


def read(ctx):
    return roofline(ctx, "qmatmul_w8a8")
