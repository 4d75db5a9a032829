"""repro — an HPX-style Asynchronous Many-Task (AMT) runtime for JAX on TPU pods.

Reproduction of: "HPX — An open source C++ Standard Library for Parallelism
and Concurrency" (Heller, Diehl, Byerly, Biddiscombe, Kaiser), adapted from a
C++ cluster runtime to a JAX/XLA TPU-pod training & serving framework.

Public API mirrors the HPX surface:

  repro.core.init / finalize / Runtime     — runtime bring-up (hpx::init)
  repro.core.spawn / async_ / dataflow     — task spawning & futurization
  repro.core.Future / when_all / when_any  — asynchronous primitives
  repro.core.agas                          — Active Global Address Space
  repro.core.parcel                        — active messages (send work to data)
  repro.core.counters                      — APEX-style performance counters
  repro.core.algorithms                    — C++17-style parallel algorithms
"""

__version__ = "1.0.0"
