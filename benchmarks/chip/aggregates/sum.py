"""SUM(column) over each group's join rows."""
import numpy as np


def program(column: str):
    from repro.aggregates.semiring import Sum

    return Sum(column)


def reference(group: np.ndarray, n: int, values: np.ndarray) -> np.ndarray:
    return np.bincount(group, weights=values, minlength=n)
