"""TPC-H Q3 on the population `presto-tpu --serve` ships
(datagen_full.py), plain numpy: q3.py's arithmetic, unchanged, over
tables this module brings itself. `TABLES` is empty, so the harness
generates nothing of datagen.py's cut population for it; the scale
factor is the parameter set's constant `sf` (stated in the mix, which
the SQL text ignores), never read off a row count."""

import numpy as np

import datagen_full
import q3

TABLES = {}
ORDER_BY = q3.ORDER_BY


def answer(_tables, p, acc=np.int64):
    return q3.answer(datagen_full.tables(float(p["sf"])), p, acc)
