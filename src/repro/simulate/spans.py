"""Profiler spans and phase scopes of the scenario engines (DESIGN.md §14).

Two kinds of marks share the ``repro/`` prefix of the dispatch scopes
(``repro/<op>/<impl>``, ``kernels.dispatch``):

* host spans (``jax.profiler.TraceAnnotation``) around the steps of one
  ``run_scenario`` call — ``repro/scenario`` encloses ``repro/host/
  prepare`` (uploads and host-side set-up), ``scan`` (the call into the
  jitted scan), ``wait`` (blocking on its outputs), ``fetch`` (copies back
  to the host) and ``serve``.  ``prepare`` and ``fetch`` carry the bytes
  they copy as the ``bytes`` arg;
* device scopes (``jax.named_scope``) ``repro/phase/<phase>`` around the
  phases of a round inside the jitted scans, so each device op's ``tf_op``
  names the phase it belongs to.

Neither has a switch: a span costs nothing while no profiler runs, and a
scope changes only the ops' metadata, never the ops.
"""

from __future__ import annotations

import jax
import numpy as np


def scenario(**args):
    """Span of one whole ``run_scenario`` call; ``args`` identify it."""
    return jax.profiler.TraceAnnotation("repro/scenario", **args)


def host(step: str, **args):
    """Span of one host step of a scenario call (``repro/host/<step>``)."""
    return jax.profiler.TraceAnnotation(f"repro/host/{step}", **args)


def phase(name: str):
    """Scope of one phase of a round inside a jitted scan."""
    return jax.named_scope(f"repro/phase/{name}")


def upload_bytes(*trees) -> int:
    """Bytes the host arrays among the leaves take on the device once
    uploaded (their dtype canonicalized as JAX does); leaves that are
    already device arrays, or not arrays, count 0."""
    return sum(x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(trees)
               if isinstance(x, np.ndarray))


def fetch_bytes(*trees) -> int:
    """Bytes of the device arrays among the leaves: what copying them to
    the host moves."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(trees)
               if isinstance(x, jax.Array))
