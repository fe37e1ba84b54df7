"""Small helpers the plain references share."""

from decimal import Decimal

import numpy as np


def days(iso_date: str) -> int:
    """Days since 1970-01-01."""
    return int(
        (np.datetime64(iso_date) - np.datetime64("1970-01-01")).astype(int)
    )


def iso(day) -> str:
    return str(np.datetime64(int(day), "D"))


def dec(scaled, scale: int) -> Decimal:
    """A decimal from its scaled integer; a float (the control's sums) is
    rounded to the nearest integer first."""
    if isinstance(scaled, (float, np.floating)):
        scaled = int(np.rint(np.float64(scaled)))
    return Decimal(int(scaled)).scaleb(-scale)


def half_up_div(total, n: int):
    """avg(decimal) as Presto rounds it: HALF_UP in scaled units. Exact
    for integers; in the control's float type for floats."""
    if isinstance(total, (float, np.floating)):
        return np.floor(total / type(total)(n) + type(total)(0.5))
    total = int(total)
    sign = -1 if total < 0 else 1
    return sign * ((2 * abs(total) + n) // (2 * n))
