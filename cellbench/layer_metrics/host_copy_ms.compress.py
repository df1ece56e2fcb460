"""The program's ``copy.to_host`` and ``copy.to_device`` spans summed per
member, in ms: the field's copy to the host in ``append`` (which also waits
for the solver step that made it) and stage 1's copies both ways (which
also wait for the kernel)."""


def read(obs):
    members = obs.counters.get("members", 0)
    spans = (obs.span_seconds("copy.to_host")
             + obs.span_seconds("copy.to_device"))
    return 1e3 * sum(spans) / members if members and spans else None
