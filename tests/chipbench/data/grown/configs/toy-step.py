"""Plain reference of ``toy-step``: the tokens after ``steps`` steps."""

import numpy as np


def reference(first, steps, cfg):
    s = cfg["step"]
    tokens = np.asarray(first, np.int64)
    for _ in range(steps):
        tokens = (tokens * s["multiplier"] + s["increment"]) % s["vocab"]
    return tokens.astype(np.int32)
