"""Mean duration of the program's ``stage1`` span per member, in ms: the
host-to-device copy, the kernel and the device-to-host copy of stage 1."""


def read(obs):
    spans = obs.span_seconds("stage1")
    return 1e3 * sum(spans) / len(spans) if spans else None
