"""Percent of the elements handed to stage 1 that are edge-block padding:
the program's ``cz_blockify_pad_elements_total`` over its
``cz_stage1_elements_total``, counted over the window."""


def read(obs):
    stage1 = obs.counters.get("stage1_elements", 0)
    if "pad_elements" not in obs.counters or not stage1:
        return None
    return 100.0 * obs.counters["pad_elements"] / stage1
