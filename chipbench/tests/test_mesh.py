"""The four-chip cell, rehearsed on four virtual XLA-CPU devices in a
process of its own (the device count is fixed before JAX starts): a sound
run takes the sharded route and is correct; with the exchange between the
chips left out (only the first chip's verdicts are gathered, the others
taken as valid), or with the flush pinned to one chip, `correct` comes out
false.  The first run compiles the sharded rung-96 program (~1 min)."""

import json
import os
import subprocess
import sys

from chipbench import manifest

SCRIPT = r'''
import json, os, sys
sys.path.insert(0, os.getcwd())
import numpy as np
from chipbench import run as runner

b = runner.Bench("commit-10k.mesh4", rehearse=True)
b.traffic = {**b.traffic, "pool": {"min_commits": 8, "cache_factor": 0.0}}
b.find_device()
b.start(False)


def window(seed, plant=None):
    d = b.build(seed)
    if seed == 1:
        b.ready(seed)
    b.warm(d)
    if plant:
        plant()
    w = b.window(d, seed, 0.0, False, min_calls=len(d.pool))
    return {"ok": w["ok"], "route": w["route"], "shards": w["shards"],
            "compared": {k: v["value"] for k, v in w["compared"].items()}}


from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto import mesh_dispatch

sound = window(1)
enqueue = mesh_dispatch.enqueue_sharded


class FirstChipOnly:
    """The pending verdicts of a sharded flush, of which only the first
    chip's shard is ever gathered."""

    def __init__(self, pending):
        self.pending = pending
        self.addressable_shards = pending.addressable_shards

    def __array__(self, dtype=None, copy=None):
        out = np.ones(self.pending.shape, dtype=bool)
        first = self.addressable_shards[0]
        out[first.index] = np.asarray(first.data)
        return out


def no_exchange():
    mesh_dispatch.enqueue_sharded = lambda mesh, rows: FirstChipOnly(enqueue(mesh, rows))


def pinned():
    mesh_dispatch.enqueue_sharded = enqueue
    os.environ["TM_TPU_MESH"] = "1"


print(json.dumps({"sound": sound, "no_exchange": window(2, no_exchange),
                  "pinned": window(3, pinned)}), flush=True)
os._exit(0)
'''


def test_sharded_route_sound_and_with_the_exchange_left_out():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           # the rehearse size (96 rows) lies under the 4 x 64 rows a sharded flush needs
           "TM_TPU_MESH_MIN_SHARD": "64"}
    env.pop("TM_TPU_MESH", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True,
                       env=env, cwd=manifest.ROOT, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    sound = got["sound"]
    assert sound["ok"] and sound["route"] == ["device", "mesh_sharded"]
    assert [rows for _dev, rows in sound["shards"]] == [24, 24, 24, 24]
    assert not any(sound["compared"].values())
    # the corrupted rows of the second half lie on the chips whose verdicts were dropped
    lost = got["no_exchange"]
    assert not lost["ok"] and lost["compared"]["calls_wrong"] >= 1
    assert lost["compared"]["route_other"] == 0
    one = got["pinned"]
    assert not one["ok"] and one["compared"]["route_other"] == 1
    assert one["compared"]["calls_wrong"] == 0      # the verdicts are right, the path is not
