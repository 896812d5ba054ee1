"""Length distributions shared by the traffic kinds: a fixed multiset of
request sizes drawn from the mix's own ``shape_seed``, so that every run
seed serves the same work in another order."""
from __future__ import annotations

import numpy as np


def lognormal(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths, lognormal around ``median`` with ``sigma``, clipped to
    ``[min, max]`` and rounded up to a multiple of ``multiple``."""
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    x = np.clip(x, spec["min"], spec["max"])
    q = spec.get("multiple", 1)
    return (np.ceil(x / q) * q).astype(np.int64)


def uniform(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths uniform over the multiples of ``multiple`` in
    ``[min, max]``."""
    q = spec.get("multiple", 1)
    choices = np.arange(spec["min"], spec["max"] + 1, q)
    return rng.choice(choices, n)


def draw(rng, spec: dict, n: int) -> np.ndarray:
    return {"lognormal": lognormal, "uniform": uniform}[spec["dist"]](
        rng, spec, n)
