"""Client protocol and result return: mean of the client's wall minus the
statement's `query` span wall."""


def compute(run):
    over = [
        r["wall_ms"] - (r["spans"]["query"][1] - r["spans"]["query"][0]) * 1e3
        for r in run.records if "query" in r.get("spans", {})
    ]
    return sum(over) / len(over) if over else None
