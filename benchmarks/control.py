"""The control of `correct`: the plain reference put in the program's
place, carried in a precision the configuration does not allow.

    python3 benchmarks/control.py --workload <cell> --seeds 1 2 3 [--acc float64 float32]

For each seed it draws the parameter sets a run of the cell would, answers
them exactly (the reference) and in each `--acc` type (the control), and
prints what `compare.verdict` makes of the control's answers: they have to
come out as not correct. Plain numpy on the host; touches no chip.
"""

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(HERE, "reference"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare  # noqa: E402
from run import reference_modules, reference_tables  # noqa: E402
from traffic import Mix, load_json  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--acc", nargs="+", default=["float64", "float32"])
    args = ap.parse_args(argv)
    cell = load_json("workloads", args.workload)
    sf = load_json("configs", cell["config"])["sf"]
    tables = None
    for seed in args.seeds:
        mix = Mix(cell["traffic"], seed)
        mods = reference_modules(mix)
        if tables is None:  # the population has no seed
            tables = reference_tables(mods, sf)
        keys = list(mix.every())
        want = {
            (st.id, i): (mods[st.id].answer(tables, st.param_sets[i]),
                         mods[st.id].ORDER_BY)
            for st, i in keys
        }
        for acc in args.acc:
            served = [
                ((st.id, i), mods[st.id].answer(
                    tables, st.param_sets[i], getattr(np, acc)))
                for st, i in keys
            ]
            correct, checks = compare.verdict(served, want, 0)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "control": acc,
                "correct": correct, "answers": len(served),
                "mismatched_cells": checks["mismatched_cells"]["value"],
                "wrong_row_count": checks["wrong_row_count"]["value"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
