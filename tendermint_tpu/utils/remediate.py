"""Self-defending node: health-detector transitions drive remediations.

PR 10's `HealthMonitor` (utils/health.py) made a node *notice* that it
is drowning — verify queue saturated, compile storm, peers flapping —
but noticing changed nothing: the mempool kept admitting, the shape
plan stayed stale, and the dialer kept feeding a flapping peer.  The
reference design treats these as first-class protocol states (mempool
`ErrMempoolIsFull` structural rejection; peer scoring/eviction around
the dial ladder), and ROADMAP item 4 names the gap: close the loop so
verdicts can assert "node shed load and stayed live" instead of "node
stalled".

`RemediationController` subscribes to detector transitions through the
monitor's `remediate` seam (`HealthMonitor.sample()` calls
`remediate.act(tr)` under the one-branch `.enabled` guard, same sink
idiom as the journal) and drives four concrete actions:

  shed     `verify_queue_saturation` warn/critical -> mempool admission
           control.  Warn sheds the lowest tx class (gossip-received)
           first; critical additionally sheds RPC-submitted txs over a
           size cutoff.  `check_tx` raises `MempoolBackpressureError`
           (a `MempoolFullError` carrying shed level + retry-after) so
           RPC surfaces a distinct backpressure error, not a generic
           internal fault.  Clear ratchets the level back down through
           the detector's own hysteresis.
  rewarm   `compile_storm` critical -> rate-limited
           `shape_plan.start_background_warm(reason="remediation",
           force=True)` — re-warm the saved plan live instead of paying
           each program's cold compile inline, at most once per
           `rewarm_min_s`.
  retune   with TM_TPU_REMEDIATE_RETUNE=1, a rewarm first folds devmon
           occupancy histograms into `consolidated_plan(device_stats)`
           (the `warm --stats` path, automated): sustained occupancy
           drift re-tunes the saved plan before the live re-warm.
  evict    `peer_flap` warn/critical -> per-peer scoring off the
           `DialBackoff` ladder's flap counters: peers at/above the
           flap threshold are disconnected and QUARANTINED from redial
           for a capped, jittered window — ending the
           dial-flap-dial loop.  On pardon (window expiry) the ladder
           is `reset()` so the peer starts from rung 0.

Every action journals a `remediation_*` event (EVENT_TYPES +
docs/observability.md schema) carrying the triggering transition's
`excused` flag — fault-window semantics identical to the health
journal rows — and steps the
`tendermint_remediation_actions_total{action,trigger}` /
`tendermint_remediation_active{action}` series (node/metrics.py;
empty-but-typed when NOP).  State surfaces in `status.health`
(`remediation` sub-block), `tendermint-tpu health`, and `top`.

Cost contract (the PR 2 sink idiom, enforced by tmlint's
`ungated-observability` for `*remediate.act`/`*remediate.record`
receivers and bench's `remediation-overhead` stage): call sites guard
with `if <remediate>.enabled:` so the disabled path costs one
attribute load + branch against the module `NOP` singleton.  Enabled
cost is per detector TRANSITION — rare by construction (hysteresis) —
never per tx or per sample.

Env knobs (resolved in `from_env`, never at import):
  TM_TPU_REMEDIATE                   default on; "0"/"false"/"off"
                                     routes every seam to NOP — node
                                     behavior bit-identical to PR 10
  TM_TPU_REMEDIATE_RETUNE            default off; enable occupancy-fed
                                     plan retuning before a rewarm
  TM_TPU_REMEDIATE_REWARM_MIN_S      min seconds between rewarms (300)
  TM_TPU_REMEDIATE_RETRY_AFTER_MS    backpressure retry hint (1000)
  TM_TPU_REMEDIATE_SHED_RPC_BYTES    critical-level RPC size cutoff
                                     (4096; smaller txs stay admitted)
  TM_TPU_REMEDIATE_FLAP_THRESHOLD    ladder flaps before eviction (3)
  TM_TPU_REMEDIATE_QUARANTINE_S      base quarantine window (30)
  TM_TPU_REMEDIATE_QUARANTINE_CAP_S  quarantine cap (120)
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from collections import deque

from tendermint_tpu.utils import clock as _clockmod

_log = logging.getLogger("tendermint_tpu.remediate")

ENV_FLAG = "TM_TPU_REMEDIATE"

OK, WARN, CRITICAL = 0, 1, 2
LEVEL_NAMES = ("ok", "warn", "critical")

#: action names (the `action` label on both metric series)
ACTIONS = ("shed", "rewarm", "retune", "evict", "pardon")

MAX_EVENTS = 128   # action history kept in memory / report()


class _NopJournal:
    enabled = False

    def log(self, event: str, **fields) -> None:
        pass


_NOP_JOURNAL = _NopJournal()


class RemediationController:
    """One node's detector->action loop.  `enabled` is True so the
    one-branch guard at call sites passes; `NOP` is the disabled twin.

    Collaborators are injected (never imported at construction):
      mempool     anything with `set_shed(level, rpc_max_bytes,
                  retry_after_ms)` and `shed_state()` — the real
                  Mempool, or None to disable the shed action
      backoff     a `p2p.backoff.DialBackoff` (peer_states()/reset())
                  feeding the flap scores, or None
      evict_peer  callable(peer_id) severing the peer now (the node
                  wires a thread-safe router disconnect); best-effort
      rewarm      callable(reason) -> bool starting a background warm;
                  defaults to `shape_plan.start_background_warm`
                  (lazy import) — tests inject a stub

    Thread model: `act()` runs on the health monitor's daemon thread;
    `quarantined()` on the dial loop; metric/status accessors on the
    scrape thread — all state mutations hold `_lock`.
    """

    enabled = True

    def __init__(self, node: str = "", *, mempool=None, backoff=None,
                 evict_peer=None, rewarm=None, journal=None,
                 retune: bool = False, rewarm_min_s: float = 300.0,
                 retry_after_ms: int = 1000, shed_rpc_max_bytes: int = 4096,
                 flap_threshold: int = 3, quarantine_s: float = 30.0,
                 quarantine_cap_s: float = 120.0,
                 rng: random.Random | None = None, clock=time.monotonic):
        self.node = node
        self.mempool = mempool
        self.backoff = backoff
        self.evict_peer = evict_peer
        self._rewarm = rewarm
        self.journal = journal if journal is not None else _NOP_JOURNAL
        self.retune = retune
        self.rewarm_min_s = rewarm_min_s
        self.retry_after_ms = int(retry_after_ms)
        self.shed_rpc_max_bytes = int(shed_rpc_max_bytes)
        self.flap_threshold = max(1, int(flap_threshold))
        self.quarantine_s = quarantine_s
        self.quarantine_cap_s = max(quarantine_s, quarantine_cap_s)
        self._rng = rng if rng is not None else random.Random(
            os.getpid() ^ id(self))
        self._clock = clock
        self._lock = threading.Lock()
        self._actions_total: dict[tuple[str, str], int] = {}
        self._events: deque = deque(maxlen=MAX_EVENTS)
        self._shed_level = 0
        self._last_rewarm: float | None = None
        self._rewarms_suppressed = 0
        # peer_id -> (quarantined_until_monotonic, consecutive evictions)
        self._quarantine: dict[str, tuple[float, int]] = {}
        self._evictions: dict[str, int] = {}

    # -- bookkeeping ----------------------------------------------------

    def _note(self, action: str, trigger: str, detail: str,
              excused: bool, **fields) -> None:
        """Count + remember + journal one executed action.  Callers
        hold no lock; journal I/O stays outside it."""
        with self._lock:
            key = (action, trigger)
            self._actions_total[key] = self._actions_total.get(key, 0) + 1
            self._events.append({
                "t": self._clock(), "w": _clockmod.wall_ns(), "action": action,
                "trigger": trigger, "detail": detail, "excused": excused,
                **fields,
            })
        if self.journal.enabled:
            self.journal.log(f"remediation_{action}", trigger=trigger,
                             detail=detail, excused=excused, **fields)

    # -- the transition sink (called by HealthMonitor.sample) -----------

    def act(self, tr: dict) -> None:
        """Handle one detector transition dict (the monitor's record:
        detector/from/to/detail/excused), or a steady re-delivery tick
        (from == to, `steady: True`) the monitor sends each sample
        while a detector stays unhealthy.  Every handler is a
        reconciler — idempotent shed, rate-limited rewarm,
        quarantine-deduped evict — so re-delivery is safe and makes the
        loop robust to state that matures AFTER the escalating
        transition (e.g. a flap score crossing its threshold mid
        incident).  Never raises — a remediation bug must not take down
        the watchdog."""
        try:
            detector = tr.get("detector", "")
            if detector == "verify_queue_saturation":
                self._act_shed(tr)
            elif detector == "compile_storm":
                self._act_rewarm(tr)
            elif detector == "peer_flap":
                self._act_evict(tr)
        except Exception as e:  # noqa: BLE001 — contain per action
            _log.warning("remediation for %s failed: %r",
                         tr.get("detector"), e)

    def record(self, name: str, value) -> None:
        """Out-of-band observation hook (sink-idiom twin of
        HealthMonitor.record; guard call sites with `.enabled`)."""
        with self._lock:
            self._events.append({
                "t": self._clock(), "w": _clockmod.wall_ns(),
                "action": "record", "trigger": name, "detail": str(value),
                "excused": False,
            })

    # -- action 1: admission control / graceful degradation --------------

    def _act_shed(self, tr: dict) -> None:
        if self.mempool is None:
            return
        level = max(OK, min(CRITICAL, int(tr.get("to", OK))))
        with self._lock:
            prev = self._shed_level
            self._shed_level = level
        if level == prev:
            return
        self.mempool.set_shed(level, rpc_max_bytes=self.shed_rpc_max_bytes,
                              retry_after_ms=self.retry_after_ms)
        self._note("shed", tr.get("detector", ""),
                   f"admission level {prev} -> {level} "
                   f"({LEVEL_NAMES[level]})",
                   bool(tr.get("excused")), level=level)

    # -- actions 2+3: compile-storm self-heal (rewarm, optional retune) --

    def _default_rewarm(self, reason: str) -> bool:
        from tendermint_tpu.ops import shape_plan as _sp

        return _sp.start_background_warm(reason, force=True)

    def _act_rewarm(self, tr: dict) -> None:
        if tr.get("to") != CRITICAL:
            return   # warn does nothing destructive; hysteresis decides
        now = self._clock()
        with self._lock:
            if (self._last_rewarm is not None
                    and now - self._last_rewarm < self.rewarm_min_s):
                self._rewarms_suppressed += 1
                return
            self._last_rewarm = now
        excused = bool(tr.get("excused"))
        if self.retune:
            self._maybe_retune(tr.get("detector", ""), excused)
        rewarm = self._rewarm or self._default_rewarm
        started = bool(rewarm("remediation"))
        self._note("rewarm", tr.get("detector", ""),
                   "background re-warm "
                   + ("started" if started else "unavailable (no saved "
                      "plan or TM_TPU_AOT=0)"),
                   excused, started=started)

    def _maybe_retune(self, trigger: str, excused: bool) -> None:
        """Fold live occupancy into the consolidated plan and save it if
        the rung set actually moved — the `warm --stats` path, automated
        (TM_TPU_REMEDIATE_RETUNE opt-in)."""
        try:
            from tendermint_tpu.ops import shape_plan as _sp
            from tendermint_tpu.utils import devmon as _dm

            stats = _dm.device_stats()
            tuned = _sp.consolidated_plan(stats)
            active = _sp.active_plan()
            if tuple(tuned.rungs) == tuple(active.rungs):
                return
            _sp.save_plan(tuned)
            _sp.reload_plan()
            self._note("retune", trigger,
                       f"shape plan retuned: {len(active.rungs)} -> "
                       f"{len(tuned.rungs)} rungs (occupancy-fed)",
                       excused, rungs=len(tuned.rungs))
        except Exception as e:  # noqa: BLE001 — retune is best-effort
            _log.warning("remediation retune failed: %r", e)

    # -- action 4: peer-flap defense -------------------------------------

    def _act_evict(self, tr: dict) -> None:
        if self.backoff is None or tr.get("to", OK) < WARN:
            return
        excused = bool(tr.get("excused"))
        now = self._clock()
        for pid, st in self.backoff.peer_states().items():
            if st.get("flaps", 0) < self.flap_threshold:
                continue
            with self._lock:
                q = self._quarantine.get(pid)
                if q is not None and now < q[0]:
                    continue   # already serving a window
                n = self._evictions.get(pid, 0) + 1
                self._evictions[pid] = n
                # capped exponential window with jitter in [1.0x, 1.5x]
                # — a repeat offender sits out longer, and a fleet of
                # evictors doesn't pardon in lock-step
                base = min(self.quarantine_cap_s,
                           self.quarantine_s * (2.0 ** (n - 1)))
                until = now + base * (1.0 + 0.5 * self._rng.random())
                self._quarantine[pid] = (until, n)
            if self.evict_peer is not None:
                try:
                    self.evict_peer(pid)
                except Exception as e:  # noqa: BLE001 — best-effort sever
                    _log.debug("evict %s failed: %r", pid[:8], e)
            self._note("evict", tr.get("detector", ""),
                       f"peer {pid[:8]} evicted after "
                       f"{st.get('flaps', 0)} flaps; quarantined "
                       f"{until - now:.1f}s (eviction #{n})",
                       excused, peer=pid[:8])

    def quarantined(self, peer_id: str) -> bool:
        """Dial-loop gate: True while `peer_id` serves a quarantine
        window.  On expiry the peer is pardoned exactly once — its
        DialBackoff ladder resets to rung 0 (the satellite fix: a
        pardoned peer must not inherit its stale rung) and a
        `remediation_pardon` event journals the release."""
        with self._lock:
            q = self._quarantine.get(peer_id)
            if q is None:
                return False
            until, n = q
            if self._clock() < until:
                return True
            del self._quarantine[peer_id]
        if self.backoff is not None:
            try:
                self.backoff.reset(peer_id)
            except Exception:  # noqa: BLE001
                pass
        self._note("pardon", "quarantine_expiry",
                   f"peer {peer_id[:8]} pardoned after eviction #{n}; "
                   "dial ladder reset to rung 0", False, peer=peer_id[:8])
        return False

    # -- views -----------------------------------------------------------

    def shed_level(self) -> int:
        with self._lock:
            return self._shed_level

    def action_samples(self) -> list:
        """[(labels, value)] rows for
        tendermint_remediation_actions_total{action,trigger}."""
        with self._lock:
            return [({"action": a, "trigger": t}, float(c))
                    for (a, t), c in sorted(self._actions_total.items())]

    def active_samples(self) -> list:
        """[(labels, value)] rows for
        tendermint_remediation_active{action}: shed = current admission
        level, evict = peers currently quarantined, rewarm = 1 while
        the rate-limit window from the last rewarm is still open."""
        now = self._clock()
        with self._lock:
            rewarm_live = (self._last_rewarm is not None
                           and now - self._last_rewarm < self.rewarm_min_s)
            return [
                ({"action": "shed"}, float(self._shed_level)),
                ({"action": "evict"},
                 float(sum(1 for until, _ in self._quarantine.values()
                           if now < until))),
                ({"action": "rewarm"}, 1.0 if rewarm_live else 0.0),
            ]

    def status_block(self) -> dict:
        """Compact block for RPC `status.health.remediation` / the
        health CLI / top."""
        now = self._clock()
        with self._lock:
            by_action: dict[str, int] = {}
            for (a, _t), c in self._actions_total.items():
                by_action[a] = by_action.get(a, 0) + c
            return {
                "enabled": True,
                "shed_level": self._shed_level,
                "shed_state": LEVEL_NAMES[self._shed_level],
                "quarantined_peers": sorted(
                    pid[:8] for pid, (until, _n) in self._quarantine.items()
                    if now < until),
                "actions_total": sum(self._actions_total.values()),
                "by_action": dict(sorted(by_action.items())),
                "rewarms_suppressed": self._rewarms_suppressed,
                "retune": self.retune,
            }

    def report(self) -> dict:
        """Full view (simnet verdict input): status + action history."""
        out = self.status_block()
        with self._lock:
            out["events"] = [dict(ev) for ev in self._events]
        return out


class _NopController:
    """Disabled controller: `.enabled` is False and every (never-taken)
    path is a no-op, so a call site costs one attribute load + branch
    and node behavior is bit-identical to the pre-remediation stack."""

    enabled = False
    mempool = None
    backoff = None

    def act(self, tr: dict) -> None:
        pass

    def record(self, name: str, value) -> None:
        pass

    def quarantined(self, peer_id: str) -> bool:
        return False

    def shed_level(self) -> int:
        return OK

    def action_samples(self) -> list:
        return []

    def active_samples(self) -> list:
        return []

    def status_block(self) -> dict:
        return {"enabled": False}

    def report(self) -> dict:
        return {"enabled": False}


NOP = _NopController()


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def env_enabled() -> bool:
    """TM_TPU_REMEDIATE gate, resolved per call (default on)."""
    return os.environ.get(ENV_FLAG, "1").lower() not in ("0", "false", "off")


def from_env(node: str = "", *, mempool=None, backoff=None, evict_peer=None,
             rewarm=None, journal=None,
             rng: random.Random | None = None,
             clock=time.monotonic) -> "RemediationController | _NopController":
    """Build a controller per TM_TPU_REMEDIATE (default ON), or return
    the NOP singleton when disabled."""
    if not env_enabled():
        return NOP
    retune = os.environ.get("TM_TPU_REMEDIATE_RETUNE", "0").lower() \
        in ("1", "true", "on")
    return RemediationController(
        node=node,
        mempool=mempool,
        backoff=backoff,
        evict_peer=evict_peer,
        rewarm=rewarm,
        journal=journal,
        retune=retune,
        rewarm_min_s=_env_float("TM_TPU_REMEDIATE_REWARM_MIN_S", 300.0),
        retry_after_ms=_env_int("TM_TPU_REMEDIATE_RETRY_AFTER_MS", 1000),
        shed_rpc_max_bytes=_env_int("TM_TPU_REMEDIATE_SHED_RPC_BYTES", 4096),
        flap_threshold=_env_int("TM_TPU_REMEDIATE_FLAP_THRESHOLD", 3),
        quarantine_s=_env_float("TM_TPU_REMEDIATE_QUARANTINE_S", 30.0),
        quarantine_cap_s=_env_float("TM_TPU_REMEDIATE_QUARANTINE_CAP_S",
                                    120.0),
        rng=rng,
        clock=clock,
    )
