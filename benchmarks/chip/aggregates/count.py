"""COUNT(*): the number of join rows in each group."""
import numpy as np


def program():
    from repro.aggregates.semiring import Count

    return Count()


def reference(group: np.ndarray, n: int, values=None) -> np.ndarray:
    return np.bincount(group, minlength=n).astype(np.float64)
