"""Open-loop Poisson arrivals at the cell's ``rate_per_s``.

``round(rate * seconds)`` arrivals; their exponential gaps are rescaled
so that all of them fall inside the window.  Every request due in the
window is waited for until its first token (``OPEN_LOOP``).
"""
import numpy as np

OPEN_LOOP = True


def count(mix: dict, rate: float, seconds: float) -> int:
    return max(1, int(round(rate * seconds)))


def gaps(rng: np.random.Generator, mix: dict, n: int,
         seconds: float) -> np.ndarray:
    # n + 1 gaps span the window, so all n arrivals fall inside it
    g = rng.exponential(1.0, size=n + 1)
    return (g * (seconds / g.sum()))[:n]
