"""TPC-H Q6 on the population `presto-tpu --serve` ships
(datagen_full.py), plain numpy: q6.py's arithmetic, unchanged, over
tables this module brings itself, as q3_full.py does. `TABLES` is empty;
the scale factor is the parameter set's constant `sf` (stated in the
mix, which the SQL text ignores)."""

import numpy as np

import datagen_full
import q6

TABLES = {}
ORDER_BY = q6.ORDER_BY


def answer(_tables, p, acc=np.int64):
    return q6.answer(datagen_full.tables(float(p["sf"])), p, acc)
