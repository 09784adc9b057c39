"""Sources uniform over the graph's n nodes: the D&A paper's query draw,
and ``PprWorkload``'s."""

import numpy as np


def draw(rng: np.random.Generator, size: int, *, n: int,
         out_degree: np.ndarray) -> np.ndarray:
    return rng.integers(0, n, size=size, dtype=np.int64)
