"""Operations and bytes of the work, computed from a configuration's sizes.

Model FLOPs count each multiply-add as 2 and leave out recomputation. The
per-token counts of an architecture live with its plain reference
(``configs/<config>.ref.py``: ``matmul_params(c)`` and
``mixer_flops_per_token(c, seq)``), so a new configuration brings its own.
"""
from __future__ import annotations

import refs


def train_flops_per_token(c, seq: int) -> float:
    """Forward and backward model FLOPs per trained token."""
    ref = refs.model_reference(c["reference"][:-len(".ref.py")])
    return 3 * (2 * ref.matmul_params(c) + ref.mixer_flops_per_token(c, seq))


def cstep_bytes(n_weights: int) -> int:
    """Compulsory HBM traffic of one C step over ``n_weights`` compressed
    float32 weights: read w and its multiplier lambda once, write Theta
    (4 bytes a weight: a float or an int32 index) and Delta(Theta) once."""
    return 16 * n_weights
