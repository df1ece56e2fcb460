"""The program's ``stage2`` spans summed per member, in ms: each member's
drain from its first chunk submitted to its last chunk written, on the wall
clock however many threads encode (``stage2_ms.compress`` sums the threads'
``encode`` time instead)."""


def read(obs):
    members = len(obs.span_seconds("stage1"))
    wall = obs.span_seconds("stage2")
    return 1e3 * sum(wall) / members if members and wall else None
