"""Lower precisions for a plain reference's operands, by name.

A reference computes in float32; a quantizer rounds an array to a lower
format and back, so a reference (``estimators/<name>.py``) or the ratio
path (``reference.Reference.ratio_nodes``) can put that format in a
product's operands and keep float32 accumulation, as the chip does it.
Which format is the configuration's and which the control's, the step
below it, is the estimator module's to say (``CONTROL``).
"""

from __future__ import annotations

import numpy as np

F32 = np.float32


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 (nearest even) and back, in integer arithmetic."""
    bits = np.ascontiguousarray(x, F32).view(np.uint32)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return rounded.view(F32)


def _fp8(x: np.ndarray) -> np.ndarray:
    """Round to float8 e4m3 (3 mantissa bits; saturating at 448) and back."""
    import ml_dtypes

    x = np.clip(np.asarray(x, F32), -448.0, 448.0)
    return x.astype(ml_dtypes.float8_e4m3fn).astype(F32)


QUANTIZERS = {None: None, "f32": None, "bf16": _bf16, "fp8": _fp8}
