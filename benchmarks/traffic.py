"""The one traffic generator: a mix is a data file under `traffic/`.

A mix names statements (`sql/<id>.sql`, `reference/<id>.py`) and says
how each draws its substitution parameters, as TPC-H's qgen does (clause
2.4). Where a new value compiles nothing (Q1's DELTA: one re-trace and a
12 ms load of the same program), `"params": {name: {"range": [lo, hi]}}`
is qgen's range of whole numbers, both ends included, and the seed draws
`distinct_per_run` sets of it. Where a new literal is a new program to
compile (Q6, Q3: PERF.md), `"sets"` is a fixed pool of whole sets: set-up
warms every one, in the file's order, and the seed only orders the
window. Either way every seed does the same amount of work of the same
kinds, set-up warms every set the window can send, and nothing compiles
in it. `{"id": ..., "as_in": <mix>}` takes a statement's parameters from
another mix's file, so two mixes share one. The statements are sent in
turn (q1, q6, q1, ...), each cycling through its sets in an order
reshuffled from the seed every cycle, by the loop the mix names
(`loops/<loop>.py`).
"""

import itertools
import json
import os
import random

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def sql_template(statement_id: str) -> str:
    with open(os.path.join(HERE, "sql", statement_id + ".sql")) as f:
        return f.read()


def draw_sets(spec: dict, rng: random.Random):
    """This run's parameter sets for one statement: the whole pool, or
    `distinct_per_run` points of the ranges' product, kept in order."""
    if "sets" in spec:
        return [dict(s) for s in spec["sets"]]
    names = sorted(spec["params"])
    space = list(itertools.product(*(
        range(int(spec["params"][n]["range"][0]),
              int(spec["params"][n]["range"][1]) + 1)
        for n in names
    )))
    k = min(int(spec["distinct_per_run"]), len(space))
    return [dict(zip(names, point)) for point in sorted(rng.sample(space, k))]


class Statement:
    """One statement class of a mix with this run's parameter sets."""

    def __init__(self, spec: dict, rng: random.Random):
        self.id = spec["id"]
        self.template = sql_template(self.id)
        if "as_in" in spec:
            spec = next(
                s for s in load_json("traffic", spec["as_in"])["statements"]
                if s["id"] == self.id
            )
        self.param_sets = draw_sets(spec, rng)
        self._rng = random.Random(rng.getrandbits(64))
        self._cycle = []

    def sql(self, i: int) -> str:
        return self.template.format(**self.param_sets[i])

    def next_index(self) -> int:
        """Cycle through this run's sets, reshuffled every cycle."""
        if not self._cycle:
            self._cycle = list(range(len(self.param_sets)))
            self._rng.shuffle(self._cycle)
        return self._cycle.pop()


class Mix:
    def __init__(self, name: str, seed: int):
        self.spec = load_json("traffic", name)
        rng = random.Random(int(seed))
        self.statements = [Statement(s, rng) for s in self.spec["statements"]]
        self._turn = []

    def every(self):
        """(statement, set index) for every set this run can send: what
        set-up warms and what the reference answers."""
        for st in self.statements:
            for i in range(len(st.param_sets)):
                yield st, i

    def __iter__(self):
        return self

    def __next__(self):
        if not self._turn:
            self._turn = list(reversed(self.statements))
        st = self._turn.pop()
        return st, st.next_index()
