"""Bytes the program's ``copy.to_host`` and ``copy.to_device`` spans moved
between host and device, over the raw bytes committed in the window."""

COPIES = ("copy.to_host", "copy.to_device")


def read(obs):
    moved = [ev["args"]["bytes"] for ev in obs.spans
             if ev.get("name") in COPIES and ev.get("ph") == "X"]
    raw = obs.counters.get("raw_bytes", 0)
    return sum(moved) / raw if moved and raw else None
