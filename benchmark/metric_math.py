"""The arithmetic the readers share: from completion stamps, spans and the
reduced trace to a metric's value. Hand-checked by the tests.
"""
from __future__ import annotations

import statistics


def window_rate(stamps: list, samples_per_round: float):
    """All the work over all the time of the window: the rounds completed
    after the first stamp, over the time from the first stamp to the last."""
    if len(stamps) < 2:
        return None
    return (len(stamps) - 1) * samples_per_round / (stamps[-1] - stamps[0])


def intervals(stamps: list) -> list:
    return [b - a for a, b in zip(stamps, stamps[1:])]


def percentile(values: list, q: float):
    """The q-th percentile by the nearest rank at or above (no
    interpolation: a tail is one of the readings)."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def median(values: list):
    return statistics.median(values) if values else None


def span_ms_per_round(run, name: str):
    """A benchmark span's time inside the window, per round of the window."""
    rounds = len(run.round_done_s) - 1
    if rounds < 1 or name not in run.spans:
        return None
    lo, hi = run.round_done_s[0], run.round_done_s[-1]
    total = sum(min(b, hi) - max(a, lo) for a, b in run.spans[name]
                if b > lo and a < hi)
    return 1e3 * total / rounds


def row_mean(run, key: str, only_positive: bool = False):
    """Mean of one field of run_loop's metrics rows inside the window."""
    vals = [row[key] for row in run.loop_rows if key in row
            and (row[key] > 0 or not only_positive)]
    return sum(vals) / len(vals) if vals else None


def idle_share(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def traced_rounds_ms(run, key: str):
    """A per-device trace total of the fullest device, per traced round."""
    if run.trace is None:
        return None
    d = run.trace["fullest"]
    return 1e3 * d[key] / d["rounds"]
