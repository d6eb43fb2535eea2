"""Plain reference of the row-sharded CL-ADMM configuration.

Sharding changes where the ADMM state lives, not what a round computes:
the sharded scenario has the single-device semantics (paper §4, Eq. 7),
so its reference is ``bench/reference/cl.py``'s ``run``, as it is.  It
runs on the first chip once the system's state is freed, beside the
inputs (x alone is ~2.5 GB there at n = 200,000, and the reference's
eight (2E, p) edge arrays and a round's temporaries ~11 GB, v5e compiler);
should that chip's memory not hold it, the same code runs on the host
CPU device.
"""

import sys

import jax
import jax.numpy as jnp

from . import cl


def run(graph, inputs: dict, config: dict, traffic, seed: int,
        dtype=jnp.float32):
    """``bench.reference.cl.run``: counters and snapshots of one call."""
    try:
        return cl.run(graph, inputs, config, traffic, seed, dtype)
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
    print("reference: the first chip's memory does not hold it; running "
          "it on the host CPU", file=sys.stderr, flush=True)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        return cl.run(jax.device_put(graph, cpu), jax.device_put(inputs, cpu),
                      config, traffic, seed, dtype)
