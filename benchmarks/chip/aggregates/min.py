"""MIN(column) over each group's join rows."""
import numpy as np


def program(column: str):
    from repro.aggregates.semiring import Min

    return Min(column)


def reference(group: np.ndarray, n: int, values: np.ndarray) -> np.ndarray:
    out = np.full(n, np.inf)
    np.minimum.at(out, group, values)
    return out
