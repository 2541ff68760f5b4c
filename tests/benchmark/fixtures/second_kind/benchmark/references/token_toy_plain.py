"""Plain reference of the ``token_toy`` configurations (tests only): numpy in
float64, a loop over the frames.  Imports nothing of the program."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def forward(sizes: Dict[str, Any], cfg: Dict[str, Any], weights,
            frames: np.ndarray) -> np.ndarray:
    """Logits ``(n, classes)`` float32 of ``frames`` ``(n, seq)`` int32."""
    del sizes, cfg
    table = np.asarray(weights["tokens"]["table"], np.float64)
    kernel, offset = (np.asarray(a, np.float64) for a in weights["out"])
    rows = [table[ids].mean(axis=0) @ kernel + offset for ids in frames]
    return np.stack(rows).astype(np.float32)
