"""lo_extdisc = lo_extendedprice x lo_discount, with lo_extendedprice =
lo_quantity x p (cents): the operand of query flight 1's sum."""

import numpy as np


def generate(quantity: np.ndarray, p: np.ndarray,
             discount: np.ndarray) -> np.ndarray:
    return quantity.astype(np.int64) * p.astype(np.int64) \
        * discount.astype(np.int64)
