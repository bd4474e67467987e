"""Plain reference for the `caffenet-tau50-avg4` configuration: the same
recipe as `caffenet-tau50` run by four workers whose weights are averaged at
the round's end and whose momentum stays their own. The model, the loss and
the Caffe SGD step are `caffenet-tau50.reference.py`'s, loaded from beside
this file; `round_reference(..., n_workers=4)` there runs one worker's round
on each chip and averages, which is what the program's four-chip round is
held to (besides the replicas being identical after the boundary average).
"""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_caffenet_tau50_reference",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "caffenet-tau50.reference.py"))
_one = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_one)

LAYERS = _one.LAYERS
init_params = _one.init_params
round_reference = _one.round_reference
CONTROL_PRECISION = _one.CONTROL_PRECISION
PROBE_LEAF = _one.PROBE_LEAF

#: as `caffenet-tau50`'s, read again on four chips (PERF.md section 2)
LIMITS = dict(_one.LIMITS)
