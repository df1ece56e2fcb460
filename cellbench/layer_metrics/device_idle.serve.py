"""Percent of the traced window in which the chip ran no operation."""
from cellbench import devtrace


def read(obs):
    return devtrace.idle_percent(obs)
