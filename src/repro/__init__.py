"""repro — OffloadFS (Moon et al., 2026) reproduced as a multi-pod JAX framework.

Two planes:
  * compute plane: model substrate + pjit/shard_map distribution for the 10
    assigned architectures (``repro.models``, ``repro.train``, ``repro.serve``).
  * I/O plane: the paper's contribution — OffloadFS / OffloadDB / OffloadPrep
    (``repro.core``, ``repro.data``) with a calibrated DES for benchmarks
    (``repro.sim``).
"""

__version__ = "1.0.0"
