"""Time a query spent waiting on other queries' work, per query, in ms:
the program's ``reader.wait`` records (waits for a ``FieldReader``'s lock,
held through each chunk's store get and decode) and ``serve.flight.wait``
spans (waits on another query's decode of the same chunk or region)."""


def read(obs):
    waits = obs.span_seconds("reader.wait")
    queries = len(obs.span_seconds("serve.query"))
    if not waits or not queries:
        return None
    return 1e3 * (sum(waits) + sum(obs.span_seconds("serve.flight.wait"))) \
        / queries
