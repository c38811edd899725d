"""Day ordinal (0 = 1992-01-01 ... 2405 = 1998-08-02) -> the date
dimension's columns, as ordinals.  What a JSON cannot say."""

import numpy as np

_YEAR_DAYS = np.array([366, 365, 365, 365, 366, 365, 365])  # 1992..1998
_YEAR_START = np.concatenate(([0], np.cumsum(_YEAR_DAYS)))
_MONTH_DAYS = np.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])


def _month_starts() -> np.ndarray:
    """First day ordinal of each of the 84 months 1992-01 .. 1998-12."""
    lens = np.tile(_MONTH_DAYS, 7)
    lens[[1, 4 * 12 + 1]] = 29          # February 1992 and 1996
    return np.concatenate(([0], np.cumsum(lens)[:-1]))


_MONTH_START = _month_starts()


def year(day: np.ndarray) -> np.ndarray:
    """d_year - 1992: 0..6."""
    return np.searchsorted(_YEAR_START, day, side="right") - 1


def yearmonth(day: np.ndarray) -> np.ndarray:
    """Months since 1992-01: 0..79 (d_yearmonthnum 199201..199808)."""
    return np.searchsorted(_MONTH_START, day, side="right") - 1


def week(day: np.ndarray) -> np.ndarray:
    """d_weeknuminyear - 1: (day of year) // 7, 0..52."""
    return (day - _YEAR_START[year(day)]) // 7
