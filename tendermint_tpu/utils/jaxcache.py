"""JAX persistent compile-cache location.

One rule: where `JAX_COMPILATION_CACHE_DIR` is set, JAX's own handling
of that variable places the cache and this module sets no directory in
code; where it is not, the cache lives at `<checkout>/.jax_cache`,
computed from this package's location — with or without a `.git`, so a
copied tree (no repository metadata) resolves the same path as the
checkout it was copied from.  Never a home directory, a temporary name,
a pid or a time: a directory that moves between runs never hits.

The directory holds deserializable compiled code (the persistent cache,
and next to it the saved shape plan and AOT executables), so it must not
be a world-writable path another user could pre-own.
"""

import logging
import os

_log = logging.getLogger("tendermint_tpu.utils.jaxcache")

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    return os.environ.get(ENV_DIR) or os.path.join(_REPO_ROOT, ".jax_cache")


def plan_path() -> str:
    """The shape plan `tendermint-tpu warm` serializes ALONGSIDE the
    compile cache (ops/shape_plan.py): the plan and the programs it
    names are one artifact — a cache warmed for plan A is cold for plan
    B, so they travel (and are placed via JAX_COMPILATION_CACHE_DIR)
    together."""
    return os.path.join(cache_dir(), "shape_plan.json")


def aot_dir() -> str:
    """Serialized ahead-of-time executables (jax.experimental
    .serialize_executable), next to the persistent cache for the same
    reason — and under the same trust model: both directories hold
    deserializable compiled code."""
    return os.path.join(cache_dir(), "aot")


def enable(jax_module) -> dict:
    """Turn the persistent compile cache on at cache_dir().

    The first device contact of a cold process pays backend init plus
    one compile per program (seconds to minutes each); the cache is what
    makes the second process cheap.  Returns — and logs — the resolved
    directory, whether it pre-existed and how many entries it held: a
    `pre_existed=False` on a deployment that should be warm is the bug
    an operator needs to see at a glance."""
    d = cache_dir()
    pre_existed = os.path.isdir(d)
    entries = 0
    if pre_existed:
        entries = sum(1 for nm in os.listdir(d) if not nm.startswith("."))
    # with the variable set before start-up jax.config already carries
    # it and nothing is set here; otherwise (unset, or set after jax was
    # imported) the config is pointed at the one resolved directory
    if jax_module.config.jax_compilation_cache_dir != d:
        jax_module.config.update("jax_compilation_cache_dir", d)
    info = {"dir": d, "pre_existed": pre_existed, "entries": entries,
            "from_env": bool(os.environ.get(ENV_DIR))}
    _log.info("jax persistent compile cache: dir=%s pre_existed=%s "
              "entries=%d from_env=%s", d, pre_existed, entries,
              info["from_env"])
    return info
