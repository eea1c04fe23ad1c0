"""What the traffic modes under ``bench/modes/`` share.

A mode is a file ``bench/modes/<name>.py`` whose class ``Mode(built,
params, seed)`` has ``setup()``, ``window(seconds)``, ``attempted``,
``e2e()``, ``reference()``, ``check(result)`` and ``control(result,
low)``.  A traffic file names its mode and holds its parameters and
correctness limits.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import find


def load(name: str):
    """The ``Mode`` class of ``bench/modes/<name>.py``."""
    return find.module("modes", name).Mode


def span(name: str):
    """A host span in the profiler's trace (``bench.*``)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def n_arrays(n_log: List[Dict[int, int]]) -> List[np.ndarray]:
    """A system's ``n_log`` (one {switch: n} per epoch) as (S,) arrays."""
    return [np.array([ns[s] for s in range(len(ns))]) for ns in n_log]
