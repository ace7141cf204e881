"""deeplearning4j_tpu — a TPU-native deep-learning framework.

Brand-new JAX/XLA/Pallas re-design with the capabilities of Deeplearning4j
(reference repo surveyed in SURVEY.md).  User surface mirrors the reference's
config-driven API (NeuralNetConfiguration builder → MultiLayerNetwork /
ComputationGraph) while the execution model is idiomatic TPU: one jitted XLA
program per train step, pytree params, mesh-sharded scale-out.
"""

import time as _time

# the first line that runs: the import's own seconds are measured from here
# (``time.perf_counter`` is what ``observability.clock.monotonic_s`` reads;
# importing that module first would run most of the import before the read)
_IMPORT_BEGAN = _time.perf_counter()

__version__ = "0.1.0"

from . import observability
from .nn.compile_cache import (persistent_cache_status,
                               wire_persistent_cache)

# persistent XLA compile cache: process restarts reload compiled
# executables from disk instead of recompiling.  The directory is
# $JAX_COMPILATION_CACHE_DIR where set, else one fixed git-ignored path in
# the checkout (nn/compile_cache.DEFAULT_CACHE_DIR).
wire_persistent_cache()

from .nn.conf.input_type import InputType
from .nn.conf.multi_layer import (MultiLayerConfiguration,
                                  NeuralNetConfiguration)
from .nn.conf.computation_graph import ComputationGraphConfiguration
from .nn.computation_graph import ComputationGraph
from .nn.multilayer import MultiLayerNetwork
from .nn.precision import PrecisionPolicy

__all__ = [
    "ComputationGraph",
    "ComputationGraphConfiguration",
    "InputType",
    "MultiLayerConfiguration",
    "NeuralNetConfiguration",
    "MultiLayerNetwork",
    "PrecisionPolicy",
    "observability",
    "persistent_cache_status",
    "wire_persistent_cache",
]

# the last line: gauges ``package_import_seconds`` and
# ``process_age_at_import_seconds`` (``observability.startup_report()``)
observability.startup.note_import(_IMPORT_BEGAN)
