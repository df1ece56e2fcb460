"""Mean of the program's ``cz_reader_decode_seconds`` over the window, in
ms: stage-2 decode, unpack and scatter, and the device inverse of a chunk."""


def read(obs):
    n = obs.counters.get("chunk_decodes", 0)
    return 1e3 * obs.counters["chunk_decode_s"] / n if n else None
