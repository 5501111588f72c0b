"""Shared benchmark utilities: CSV emission per the brief
(``name,us_per_call,derived``)."""

from __future__ import annotations

import sys
import time
from typing import Callable, Optional

import jax


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")
    sys.stdout.flush()


def time_fn(fn: Callable, repeats: int = 3, warmup: int = 1) -> float:
    """Median wall time of fn() in microseconds; each call's result is
    waited on (``jax.block_until_ready``) before the clock is read, so the
    time covers the device work and not only its dispatch."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]
