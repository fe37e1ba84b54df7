"""Geometric mean over the statement classes of each class's mean client
wall (the core of TPC-H's Power metric, clause 5.4.1)."""

from stats import class_means, geomean


def compute(run):
    return geomean(class_means(run.records).values())
