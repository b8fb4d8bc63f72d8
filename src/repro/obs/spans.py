"""Stage names of the SAFL round, and the driver's host spans (DESIGN.md §11).

Two kinds of name live here, one constant each, so the program and anything
that reads a profile agree letter for letter:

* **Stage scopes** (``jax.named_scope``): each wraps the body of the
  function that does the stage, so every caller's compiled program carries
  the scope in its ops' ``op_name`` metadata.  Scopes are metadata only:
  they add no op and change no number, so unlike a ``Telemetry`` probe they
  start no program family.
* **Host spans** (``span``): ``jax.profiler.TraceAnnotation`` around the
  driver's per-chunk phases.  They land in a profile on the same clock as
  the device's ops and cost nothing when no profiler is running.
"""

from __future__ import annotations

import jax

# stage scopes
CLIENT = "safl.client"          # K local SGD steps and x_0 - x_K
DERIVE = "safl.derive"          # the round's hashes, signs, SRHT params
SKETCH = "safl.sketch"          # the (G, b_total) payload
MEAN = "safl.mean"              # the cohort mean: the one cross-client sum
DESK = "safl.desk"              # payload back to R^d
SERVER_OPT = "safl.server_opt"  # ADA_OPT
SAMPLE = "driver.sample"        # the on-device batch draw
STAGES = (CLIENT, DERIVE, SKETCH, MEAN, DESK, SERVER_OPT, SAMPLE)

# host spans of ``launch.driver.run_scan``, one each per chunk
DISPATCH = "run_scan.dispatch"  # the call into the chunk (holds any compile)
FETCH = "run_scan.fetch"        # the history's device->host copy
ON_CHUNK = "run_scan.on_chunk"  # the caller's callback
HOST_SPANS = (DISPATCH, FETCH, ON_CHUNK)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span on the profiler's clock; ``meta`` become its stats."""
    return jax.profiler.TraceAnnotation(name, **meta)
