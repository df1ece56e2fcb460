"""Percent of the device's idle time in the window during which no program
span was open on any host thread (``cellbench/hostspans.py``)."""
from cellbench import hostspans


def read(obs):
    return hostspans.unattributed_percent(obs)
