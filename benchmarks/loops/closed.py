"""The closed loop: each client sends its next statement when the last
one's rows are in. `"loop": "closed"` in a traffic file names this
module; another arrival process is another module here, found by name."""

import time


def drive(mix, new_client, send, seconds: float, tracer):
    """Send `mix`'s statements until `seconds` have passed; the
    statement in flight then is finished and counted. `send(client,
    statement, set index, annotate)` returns the statement's record;
    `tracer.due(elapsed, statements)` stops a running trace once it has
    what it needs. Returns the records in the order sent."""
    if mix.spec["clients"] != 1:
        raise ValueError("the closed loop drives one client")
    client = new_client()
    records = []
    t_open = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - t_open
        if elapsed >= seconds:
            return records
        tracer.due(elapsed, len(records))
        st, i = next(mix)
        records.append(send(client, st, i, tracer.annotate))
