"""MAX(column) over each group's join rows."""
import numpy as np


def program(column: str):
    from repro.aggregates.semiring import Max

    return Max(column)


def reference(group: np.ndarray, n: int, values: np.ndarray) -> np.ndarray:
    out = np.full(n, -np.inf)
    np.maximum.at(out, group, values)
    return out
