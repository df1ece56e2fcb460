"""The program's ``pad`` spans summed per member, in ms: the writes that
fill a field's edge blocks by replicating its last plane, inside
``blockify`` of a host field.  A field blockified on the device opens no
such span (its pad is an op of the device trace)."""


def read(obs):
    members = obs.counters.get("members", 0)
    spans = obs.span_seconds("pad")
    return 1e3 * sum(spans) / members if members and spans else None
