"""The comparison that decides ``correct``.

What is compared is what the timed path itself produced at the timed sizes:
the logits rows that ``Pipeline.run`` delivered at batch = streams, against
the plain reference run over the same frames once the window has closed.

``logit_err``: the worst sampled frame's ``||program - reference||`` over
the spread of the sample's references about their mean (root mean square of
``||reference_i - mean||``).  The spread, not the norm, is the yardstick
because a mean over tokens leaves every frame's logits close to one common
vector: measured against the norm, a row routed to the wrong stream would
pass.  Against the spread a swapped row reads about 1.4, bf16 rounding reads
well under the limit, and the W8A8 control over it (readings in PERF.md).
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def logit_err(program: np.ndarray, reference: np.ndarray) -> float:
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape or program.ndim != 2 or len(program) < 2:
        raise ValueError(f"cannot compare {program.shape} with {reference.shape}")
    if not np.isfinite(program).all():
        return float("inf")
    centred = reference - reference.mean(axis=0, keepdims=True)
    spread = float(np.sqrt((np.linalg.norm(centred, axis=1) ** 2).mean()))
    if spread <= 0:
        raise ValueError("the sampled references are all alike")
    return float(np.linalg.norm(program - reference, axis=1).max() / spread)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit, in a fixed order."""
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in numbers}


def passes(compared: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in compared.values())
