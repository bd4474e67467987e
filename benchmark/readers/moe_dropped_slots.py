"""Routed slots discarded for want of room in the window's rounds, all expert
layers together: the program's own counters, summed on the device inside the
round and fetched one round late with the loss. Anything but 0 is a fault
(the check round's own count is an exact check of `correct`)."""
from __future__ import annotations


def read(run):
    return run.notes.get("moe", {}).get("slots_dropped")
