"""A backlog: ``count`` requests all due at the window's start, more than
a window drains.  Only the requests the window starts are attempted
(not ``OPEN_LOOP``); a mix sets ``shuffle_block`` so that the seed
reorders sizes within blocks and every seed's head holds the same work.
"""
import numpy as np

OPEN_LOOP = False


def count(mix: dict, rate: float, seconds: float) -> int:
    return int(mix["count"])


def gaps(rng: np.random.Generator, mix: dict, n: int,
         seconds: float) -> np.ndarray:
    return np.zeros(n)
