"""Parse + plan: mean `plan` span wall (session.py)."""

from stats import mean_span_ms


def compute(run):
    return mean_span_ms(run.records, "plan")
