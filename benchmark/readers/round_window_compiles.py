"""Executables built or fetched inside the window, on any thread."""
from __future__ import annotations


def read(run):
    return run.compiles_in_window
