"""Device (JAX/XLA) kernels: the crypto data plane.

The field arithmetic uses 64-bit integer lanes; enable x64 before any
tracing.  This must happen before the first jitted call in the process.
"""

import jax

from tendermint_tpu.utils import jaxcache

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the verifier's scalar-mul loop is a large
# program; caching its binary makes test sessions and bench reruns cheap.
jaxcache.enable(jax)
