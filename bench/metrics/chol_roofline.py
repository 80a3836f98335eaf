"""chol_roofline: least time of the anchor factorizations (g·k·h³/3
flops at the bf16 peak, or their bytes at HBM bandwidth) over their device
time, in percent."""
from bench.readers import roofline_pct


def read(m):
    return roofline_pct(m, "chol")
