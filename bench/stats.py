"""Numbers as the result line holds them."""
from __future__ import annotations

import math
import sys


def finite(x: float) -> float:
    """A number JSON can hold: +-inf become the largest float."""
    if math.isnan(x):
        raise ValueError("nan")
    return max(-sys.float_info.max, min(sys.float_info.max, x))
