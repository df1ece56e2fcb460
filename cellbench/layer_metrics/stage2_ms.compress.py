"""The program's ``encode`` records summed per member, in ms: serialize,
shuffle and the stage-2 coder of every chunk of one member."""


def read(obs):
    members = len(obs.span_seconds("stage1"))
    encode = obs.span_seconds("encode")
    return 1e3 * sum(encode) / members if members and encode else None
