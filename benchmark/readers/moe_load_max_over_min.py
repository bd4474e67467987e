"""Tokens of the fullest over tokens of the emptiest expert this chip holds,
mean over the expert layers and the window's rounds: the program's counters."""
from __future__ import annotations


def read(run):
    return run.notes.get("moe", {}).get("load_max_over_min")
