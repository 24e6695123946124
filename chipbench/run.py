#!/usr/bin/env python3
"""One cell of the benchmark, once:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds the chips the cell asks for (fewer: exit 3, a line
on stderr, no result), builds the cell's data from --seed through the
configuration's entry point (chipbench/entries/<entry>.py), starts the
verifier and the service as `Node.start()` does, waits for the device,
warms the cell's own rung through the bound entry point, measures for
--seconds, checks every call against the plain reference, prints one JSON
object as the last line of stdout and leaves through os._exit so that no
daemon thread outlives it holding the chip.

Exit codes: 0 a result was printed (read its `correct`); 2 usage or
manifest; 3 no chip; 4 set-up failed; 5 the window failed; 6 the trace
could not be read.  Every non-zero exit names its stage on stderr and
prints no result line.  See chipbench/README.md.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as nearly as Python can tell

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import correct, generator, manifest, tracing  # noqa: E402
from chipbench.observe import Observation  # noqa: E402

EXIT_USAGE, EXIT_NO_CHIP, EXIT_SETUP, EXIT_WINDOW, EXIT_TRACE = 2, 3, 4, 5, 6
HARD_LIMIT_S = 1150.0
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chipbench")


class StageFailed(Exception):
    def __init__(self, code: int, stage: str, why: str):
        super().__init__(why)
        self.code, self.stage, self.why = code, stage, why


def say(msg: str) -> None:
    print(f"[chipbench +{time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class GcPauses:
    """Collections of the cyclic collector while a window runs, by
    generation: how many and how long (a diagnostic on the summary line)."""

    def __init__(self):
        self.seen = {g: [0, 0.0] for g in (0, 1, 2)}
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            cell = self.seen[info["generation"]]
            cell[0] += 1
            cell[1] += time.perf_counter() - self._t

    def stop(self) -> dict:
        gc.callbacks.remove(self._cb)
        return {f"gen{g}": {"collections": n, "seconds": round(s, 4)}
                for g, (n, s) in self.seen.items()}


class StallWatch:
    """Names what a stalled call was doing: when a call has run longer
    than `after_s`, the stacks of all threads go to stderr, once per
    window.  One sleeping thread, woken four times a second."""

    def __init__(self, call, after_s: float = 1.0):
        self._call, self._after = call, after_s
        self._started: dict[int, float] = {}   # caller thread -> its call's start
        self._done = threading.Event()
        self.dumped = 0
        self._thread = threading.Thread(target=self._run, name="chipbench-stallwatch",
                                        daemon=True)
        self._thread.start()

    def __call__(self, item):
        me = threading.get_ident()
        self._started[me] = time.perf_counter()
        try:
            return self._call(item)
        finally:
            self._started.pop(me, None)

    def _run(self) -> None:
        while not self._done.wait(0.25):
            t = min(list(self._started.values()), default=None)
            if t is not None and not self.dumped and time.perf_counter() - t > self._after:
                self.dumped += 1
                say(f"a call has been running for over {self._after:g}s; stacks of all threads:")
                faulthandler.dump_traceback(file=sys.stderr, all_threads=True)

    def stop(self) -> None:
        self._done.set()
        self._thread.join()


class Bench:
    """The stages of a run, reusable: `prove.py` starts the system once
    and measures many seeds; `run.py` does one."""

    def __init__(self, workload: str, rehearse: bool = False):
        try:
            self.manifest = manifest.load()
            self.cell = manifest.cell(self.manifest, workload)
            self.entry = manifest.entry(self.cell["config_file"]["entry"])
        except (OSError, ValueError, KeyError, manifest.ManifestError) as e:
            raise StageFailed(EXIT_USAGE, "manifest", f"{type(e).__name__}: {e}")
        self.rehearse = rehearse
        self.cfg = self.cell["config_file"]
        self.traffic = self.cell["traffic_file"]
        self.sizes = ({**self.cfg, **self.cfg["rehearse"]} if rehearse else self.cfg)
        self.device = None
        self.system = None
        self.watch = None

    # -- stages --------------------------------------------------------
    def find_device(self) -> None:
        try:
            import tendermint_tpu  # noqa: F401 — outside a checkout this fails
        except ImportError as e:
            raise StageFailed(EXIT_SETUP, "import", f"the program is not here: {e}")
        from chipbench import system

        try:
            self.device = system.find_device(self.cell["chips"], self.rehearse)
        except system.NoChip as e:
            raise StageFailed(EXIT_NO_CHIP, "device", str(e))

    def start(self, trace_on: bool) -> None:
        from chipbench import system

        try:
            self.system = system.start(trace_on)
            self.watch = system.CompileWatch()
        except system.SetupFailed as e:
            raise StageFailed(EXIT_SETUP, "start", str(e))
        say(f"system started: {json.dumps(self.system)}")

    def build(self, seed: int):
        t0 = time.monotonic()
        d = self.entry.build(seed, self.cfg, self.sizes, self.system["cache_capacity"],
                             self.traffic["pool"], self.traffic["warmup_commits"])
        rows = [item.n_rows for item in d.pool]
        say(f"data from seed {seed}: pool of {len(rows)} items, {min(rows)} to "
            f"{max(rows)} rows consulted per call ({sum(rows)} signatures against "
            f"a cache of {self.system['cache_capacity']}), {len(d.warmup)} "
            f"warm-up items, built in {time.monotonic() - t0:.1f}s")
        return d

    def ready(self, seed: int) -> None:
        from chipbench import system

        try:
            info = system.wait_ready(seed)
        except system.SetupFailed as e:
            raise StageFailed(EXIT_SETUP, "readiness", str(e))
        self.system.update(info)
        say(f"device ready: {json.dumps(info)}")

    def warm(self, d) -> None:
        """The cell's own rung and no other: the warm-up items through the
        bound entry point itself, each required to be accepted along the
        entry's path (every path number 0; the compiles are what a warm-up
        is for and are not counted)."""
        from chipbench import system

        call = self.entry.bind(d)
        for item in d.warmup:
            before = system.counters()
            made = generator.timed(call, -1, item)
            after = system.counters()
            route = system.last_route()
            numbers = self.entry.path(before, after, [made], 0, route,
                                      self.device["count"])
            if made.outcome != ("accept", None) or any(numbers.values()):
                raise StageFailed(
                    EXIT_SETUP, "warm-up",
                    f"warm-up call of {item.n_rows} rows did not resolve along "
                    f"the entry's path: outcome {made.outcome}, path numbers "
                    f"{numbers}, route {route}, counters {after}, threshold "
                    f"{self.system.get('threshold')}")
        programs = [{"rung": e["rung"], "impl": e["impl"], "source": e["source"],
                     "first_call_s": e["seconds"]} for e in system.compile_events()]
        self.system["programs"] = programs
        say(f"warm: {json.dumps(programs)}")

    def window(self, d, seed: int, seconds: float, trace: bool,
               min_calls: int = 0) -> dict:
        """Measure, then check.  Returns everything a result line needs."""
        from chipbench import system

        sl = None
        if trace:
            tr = self.traffic["trace"]
            sl = tracing.Slice(os.path.join(OUT_DIR, f"{self.cell['name']}.trace"),
                               tr["lead_s"], tr["slice_s"], tr["min_flushes"],
                               tr["max_flushes"], chips=self.device["count"])
        before = system.counters()
        pauses = GcPauses()
        watched = StallWatch(self.entry.bind(d))
        try:
            calls, t0, t1 = generator.run_window(
                self.traffic, d.pool, watched, seconds,
                between=sl.between if sl else None, min_calls=min_calls)
            if sl:
                sl.stop()
        except Exception as e:  # noqa: BLE001 — the generator itself failed
            raise StageFailed(EXIT_WINDOW, "window", f"{type(e).__name__}: {e}")
        finally:
            watched.stop()
        gc_seen = pauses.stop()
        after = system.counters()
        route = system.last_route()
        shards = system.last_shard_layout()
        peak = system.memory_peak_bytes()
        if not calls:
            raise StageFailed(EXIT_WINDOW, "window", "no call was made")
        compiles = self.watch.between(t0, t1)
        obs = Observation(
            cell=self.cell, device=self.device, calls=calls, window_s=t1 - t0,
            before=before, after=after, compiles_in_window=compiles, spans=[],
            trace=None, slice=None)
        if trace:
            obs.spans = system.spans_since(int(t0 * 1e9))
            obs.trace, obs.slice = self._reduce(sl, obs, calls)
        numbers = correct.check_calls(self.entry, d, calls, seed)
        numbers.update(self.entry.path(before, after, calls, compiles, route,
                                       self.device["count"]))
        ok, compared = correct.compared(numbers)
        return {"obs": obs, "ok": ok, "compared": compared,
                "detail": numbers["detail"], "peak": peak, "t0": t0, "gc": gc_seen,
                "route": route, "shards": shards,
                "failed": numbers["calls_wrong"]}

    def _reduce(self, sl, obs, calls):
        if sl.t_on is None or sl.t_off is None:
            raise StageFailed(EXIT_TRACE, "trace", "the window ended before "
                              "the traced slice began (--seconds too short)")
        call_spans = [{"name": "harness.call (outside the service's spans)",
                       "t0_ns": c.t_start * 1e9, "dur_ns": c.seconds * 1e9}
                      for c in calls]
        try:
            events = tracing.load_events(sl.out_dir, rehearse=self.rehearse)
            red = tracing.reduce(events, sl.t_on, sl.t_off, sl.sync_perf_ns,
                                 spans=obs.spans + call_spans,
                                 chips=self.device["count"])
        except tracing.TraceUnreadable as e:
            raise StageFailed(EXIT_TRACE, "trace", str(e))
        except Exception as e:  # noqa: BLE001 — a trace the reader chokes on
            raise StageFailed(EXIT_TRACE, "trace", f"{type(e).__name__}: {e}")
        if len(red.program_events) < sl.min_flushes:
            raise StageFailed(
                EXIT_TRACE, "trace", f"only {len(red.program_events)} whole flushes of "
                f"the program in the slice, {sl.min_flushes} needed")
        if os.environ.get("CHIPBENCH_KEEP_TRACE"):
            # a builder's aid: an excerpt small enough to keep as a test's
            # recorded trace; the raw trace stays for a look by hand
            with gzip.open(sl.out_dir + ".recorded.json.gz", "wt") as fh:
                json.dump(tracing.excerpt(events, sl.t_on, sl.sync_perf_ns), fh)
        else:
            shutil.rmtree(sl.out_dir, ignore_errors=True)
        return red, (sl.t_on, sl.t_off)

    # -- the result ----------------------------------------------------
    def end_to_end(self, w: dict, setup_s: float) -> dict:
        obs = w["obs"]
        lat = [c.seconds * 1e3 for c in obs.calls]
        values = {
            "sigs_per_s": obs.rows() / obs.window_s,
            "verify_p50_ms": percentile(lat, 0.50),
            "verify_p95_ms": percentile(lat, 0.95),
            "setup_s": setup_s,
        }
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in manifest.end_to_end(self.manifest, self.cell["name"])}

    def per_layer(self, w: dict) -> dict:
        out = {}
        for m in manifest.per_layer(self.manifest, self.cell["name"]):
            try:
                value = manifest.reader(m["name"])(w["obs"])
            except KeyError as e:
                if not self.rehearse:   # e.g. a device with no published peaks
                    raise
                say(f"rehearsal: {m['name']} left out ({e})")
                continue
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def result(self, w: dict, setup_s: float, trace: bool) -> dict:
        obs = w["obs"]
        device = {**self.device, "memory_peak_bytes": w["peak"]}
        res = {"correct": bool(w["ok"]), "attempted": len(obs.calls),
               "failed": w["failed"]}
        if trace:
            res["metrics"] = self.per_layer(w)
            device["busy_s"] = obs.trace.busy_s
            device["window_s"] = obs.trace.window_s
            res["breakdown"] = {"device_ops": obs.trace.device_ops,
                                "idle_gaps": obs.trace.idle_gaps}
        else:
            res["metrics"] = self.end_to_end(w, setup_s)
        res["device"] = device
        if self.rehearse:
            res["rehearsal"] = "XLA-CPU at toy sizes: control flow only, no number here is a device metric"
        res["compared"] = w["compared"]
        return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="XLA-CPU at the configuration's rehearse sizes; "
                         "control flow only, never a device number")
    args = ap.parse_args()
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(levelname).1s %(name)s | %(message)s")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        bench = Bench(args.workload, args.rehearse)
        bench.find_device()
        say(f"device: {bench.device}")
        bench.start(bool(args.trace))
        d = bench.build(args.seed)
        bench.ready(args.seed)
        bench.warm(d)
        # the pool (some 10^5 objects a node would never hold at once) must
        # not weigh on every collection the window's allocations trigger
        gc.collect()
        gc.freeze()
        setup_s = time.monotonic() - T_START
        say(f"set-up done in {setup_s:.1f}s; measuring {args.seconds:g}s")
        w = bench.window(d, args.seed, args.seconds, bool(args.trace))
        res = bench.result(w, setup_s, bool(args.trace))
    except StageFailed as e:
        print(f"chipbench: stage {e.stage!r} failed (exit {e.code}): {e.why}",
              file=sys.stderr, flush=True)
        return e.code
    obs = w["obs"]
    slowest = sorted(obs.calls, key=lambda c: -c.seconds)[:8]
    print("summary: " + json.dumps({
        "workload": args.workload, "seed": args.seed, "calls": len(obs.calls),
        "window_s": obs.window_s, "rows_per_call": obs.rows() / len(obs.calls),
        "setup_s": setup_s, "system": bench.system, "check_detail": w["detail"],
        "last_route": w["route"], "last_shard_layout": w["shards"],
        # diagnostics, not metrics: where in the window the slowest calls
        # lay, and what the collector did meanwhile
        "slowest_calls": [{"at_s": round(c.t_start - w["t0"], 3),
                           "ms": round(c.seconds * 1e3, 3), "item": c.item,
                           "outcome": c.outcome[0]} for c in slowest],
        "sum_of_calls_s": sum(c.seconds for c in obs.calls),
        "gc_in_window": w["gc"],
    }, default=str), flush=True)
    for name, e in res["compared"].items():
        print(f"compared {name}: {e['value']} (limit {e['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # no daemon thread, pool or profiler server may outlive the run
    # holding the chip
    os._exit(code)
