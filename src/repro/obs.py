"""Names of the program's device scopes and host spans, and the span helper.

Device scopes (``jax.named_scope``) land in each compiled instruction's
``op_name`` and cost nothing at run time.  Under ``value_and_grad`` the
forward scope reads ``jvp(fwd)``, and the backward ops read
``transpose(jvp(fwd))``.  Host spans (``jax.profiler.TraceAnnotation``)
are recorded only while a profiler session is active; their counters are
event stats of the span.  ``docs/serving.md`` says what each one covers.
"""

from __future__ import annotations

import jax

__all__ = ["FWD", "OPTIMIZER", "MATERIALIZE", "SAMPLE", "STEP", "ADMIT",
           "PREFILL", "DECODE", "DRAIN", "RETIRE", "span"]

# device scopes
FWD = "fwd"                  # the loss under differentiation
OPTIMIZER = "optimizer"      # the optimizer update
MATERIALIZE = "materialize"  # serving weights to the matmul dtype
SAMPLE = "sample"            # temperature / top-k sampling

# host spans of ServeEngine.step
STEP = "repro.serve.step"        # one tick
ADMIT = "repro.serve.admit"      # queue to slots, host arrays
PREFILL = "repro.serve.prefill"  # upload, dispatch, first tokens back
DECODE = "repro.serve.decode"    # argument upload, dispatch
DRAIN = "repro.serve.drain"      # read-back of the chunk
RETIRE = "repro.serve.retire"    # tokens to requests, retirements


def span(name: str, **counters) -> jax.profiler.TraceAnnotation:
    """A host span with numeric counters; a counter known only at the end
    is set with ``.set_metadata(k=v)`` on the entered span."""
    return jax.profiler.TraceAnnotation(name, **counters)
