"""kappa(x, y) = exp(-alpha (x - y)^2) (paper Appendix B, example 1).

Exact: ``rank`` and ``domain`` are settings of the program's truncated
feature expansion, which the reference does not share.
"""
import numpy as np

# difference, square, scale and exponential, per evaluation (work model)
FLOPS = 4


def kappa(x, y, alpha: float, rank: int = 0, domain: float = 0.0):
    d = np.asarray(x, np.float64) - np.asarray(y, np.float64)
    return np.exp(-float(alpha) * d * d)
