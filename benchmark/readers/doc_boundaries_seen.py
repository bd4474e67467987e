"""Document boundaries a round as the program's mixers counted them (the
counter `doc_boundaries` a Mamba-2 mixer returns under document ids: the
positions whose document differs from the one before's, as the scan's and the
taps' cut saw them), the mean over the window's rounds: summed on the device
inside the round and fetched one round late with the loss. Nothing to read
from a program whose mixers count nothing."""
from __future__ import annotations


def read(run):
    return (run.notes.get("doc_boundaries") or {}).get("per_round")
