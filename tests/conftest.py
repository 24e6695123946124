"""Test environment: force JAX onto a virtual 8-device CPU platform so the
multi-chip sharding paths compile/execute without TPU hardware.

Must run before the first backend initialization: the platform is set
in the environment AND through jax.config (which still works as long as
no device has been touched yet, whoever imported jax first).  Set
TM_TPU_TEST_PLATFORM=tpu to deliberately run the suite on an attached
TPU — one pytest process then holds the chip, so the tests that start
node processes keep their children on the host verifier.
"""

import os

_platform = os.environ.get("TM_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)
# persistent compile cache: an XLA-CPU compile of a verify program
# costs ~1 min; the cache turns suite re-runs from hours into minutes.
# Same dir as bench.py / __graft_entry__ (utils/jaxcache).
from tendermint_tpu.utils import jaxcache  # noqa: E402

jaxcache.enable(jax)

# opt-in runtime lock-order checking for the whole suite: set
# TM_TPU_LOCKCHECK=1 and every threading.Lock/RLock created from here
# on is order-checked (utils/lockcheck; the async-verify and multinode
# modules install it per-test regardless).
from tendermint_tpu.utils import lockcheck  # noqa: E402

lockcheck.maybe_install_from_env()

# opt-in lockset race sanitizing the same way: TM_TPU_RACECHECK=1
# instruments the registered thread-shared classes for the whole suite
# (utils/racecheck; the async_verify/multinode/health/history/remediate
# modules install it per-test regardless).
from tendermint_tpu.utils import racecheck  # noqa: E402

racecheck.maybe_install_from_env()
