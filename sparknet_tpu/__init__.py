"""sparknet_tpu — a TPU-native distributed deep-network training framework.

Built from scratch (JAX/XLA/Pallas/pjit) with the capabilities of the
reference SparkNet (AMPLab, arXiv:1511.06051): declarative model specs
compiled to XLA, Caffe-semantics SGD, schema-driven data loading, and
data-parallel τ-local-step parameter-averaging training where weight sync is
an on-device `pmean` over the ICI mesh rather than a driver round trip.
"""

__version__ = "0.1.0"

import time as _time

#: `time.perf_counter()` at this process's first import of the package,
#: before any of its modules (or jax) is loaded: where a start-up account
#: begins (`obs.trace.import_stamp()`)
IMPORT_T0 = _time.perf_counter()

from .model.spec import NetSpec, LayerSpec, InputSpec  # noqa: F401
from .model.net import CompiledNet  # noqa: F401
from .model.prototxt import (  # noqa: F401
    net_from_prototxt,
    net_from_prototxt_file,
    solver_from_prototxt,
    solver_from_prototxt_file,
)
