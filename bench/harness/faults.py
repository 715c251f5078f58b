"""Faults planted under the timed path, for the tests and the control
tool: each runs a method of the program's ``BatchedPredictor`` unchanged
and alters what it produced."""
from __future__ import annotations

from typing import Callable

import numpy as np


def altered_answer(a: np.ndarray) -> np.ndarray:
    """A clip's answer altered where the device batch is retired."""
    a[::4] *= 2.0
    return a


def half_batch(a: np.ndarray) -> np.ndarray:
    """Half of the batch a pass or a flush drains left out, its clips
    given the mean of the rest."""
    h = len(a) // 2
    if h:
        a[h:] = a[:h].mean()
    return a


FAULTS = {"answer_altered": ("_retire", altered_answer),
          "half_batch_mean": ("drain", half_batch)}


def wrapped(name: str):
    """(method name, the method with fault ``name`` planted in it)."""
    from repro.core.engine import BatchedPredictor
    method, alter = FAULTS[name]
    orig = getattr(BatchedPredictor, method)

    def retire(self):
        orig(self)
        self._retired[-1] = alter(np.array(self._retired[-1]))

    def drain(self):
        return alter(np.array(orig(self)))
    return method, {"_retire": retire, "drain": drain}[method]


def plant(name: str) -> Callable[[], None]:
    """Plant fault ``name``; returns the call that takes it out."""
    from repro.core.engine import BatchedPredictor
    method, fn = wrapped(name)
    orig = getattr(BatchedPredictor, method)
    setattr(BatchedPredictor, method, fn)
    return lambda: setattr(BatchedPredictor, method, orig)
