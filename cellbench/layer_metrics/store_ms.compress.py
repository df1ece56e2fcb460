"""The program's ``store.write`` spans (each chunk's write, the footer and
its fsync) and ``store.commit`` spans (the manifest's atomic replace),
summed per member, in ms."""


def read(obs):
    members = obs.counters.get("members", 0)
    spans = obs.span_seconds("store.write") + obs.span_seconds("store.commit")
    return 1e3 * sum(spans) / members if members and spans else None
