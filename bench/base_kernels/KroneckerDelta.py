"""kappa(x, y) = 1 if x == y else h: vertex labels as element codes."""
import numpy as np

# comparison and select, per evaluation (work model)
FLOPS = 1


def kappa(x, y, h: float, n_labels: int = 0):
    return np.where(np.asarray(x) == np.asarray(y), 1.0, float(h))
