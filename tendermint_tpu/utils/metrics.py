"""Minimal Prometheus instrumentation: Counter/Gauge/Histogram with
labels, a Registry, and text exposition over HTTP.

Parity: reference uses prometheus/client_golang behind per-subsystem
Metrics structs (consensus/metrics.go:77-186, p2p/metrics.go,
mempool/metrics.go, state/metrics.go) served at
InstrumentationConfig.PrometheusListenAddr (node/node.go:925-928).
The image ships no Python prometheus client, so the text format
(exposition 0.0.4) is rendered by hand.

Gauges may be backed by a callback evaluated at scrape time, which keeps
hot paths untouched for point-in-time values (height, mempool size,
peer count).
"""

from __future__ import annotations

import time
from typing import Callable

_DEFAULT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{str(v).replace(chr(92), chr(92)*2).replace(chr(34), chr(92)+chr(34))}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(v)


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str = "", namespace: str = "",
                 subsystem: str = ""):
        parts = [p for p in (namespace, subsystem, name) if p]
        self.name = "_".join(parts)
        self.help = help_

    def samples(self) -> list[tuple[str, dict, float]]:
        raise NotImplementedError

    def expose(self) -> str:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        for suffix, labels, value in self.samples():
            lines.append(f"{self.name}{suffix}{_fmt_labels(labels)} {_fmt_value(value)}")
        return "\n".join(lines)


class Counter(_Metric):
    kind = "counter"

    def __init__(self, *args, label_names: tuple[str, ...] = (), **kw):
        super().__init__(*args, **kw)
        self.label_names = label_names
        self._values: dict[tuple, float] = {}

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = tuple(labels.get(n, "") for n in self.label_names)
        self._values[key] = self._values.get(key, 0.0) + amount

    def samples(self):
        if not self._values:
            return [("", {}, 0.0)] if not self.label_names else []
        return [("", dict(zip(self.label_names, k)), v)
                for k, v in sorted(self._values.items())]


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, *args, fn: Callable[[], float] | None = None,
                 label_names: tuple[str, ...] = (), **kw):
        super().__init__(*args, **kw)
        self.label_names = label_names
        self._fn = fn
        self._values: dict[tuple, float] = {}

    def set(self, value: float, **labels) -> None:
        key = tuple(labels.get(n, "") for n in self.label_names)
        self._values[key] = float(value)

    def add(self, amount: float = 1.0, **labels) -> None:
        key = tuple(labels.get(n, "") for n in self.label_names)
        self._values[key] = self._values.get(key, 0.0) + amount

    def samples(self):
        if self._fn is not None:
            # a callback raising at scrape time (e.g. a round-state field
            # read mid-transition) omits THIS sample; the rest of the
            # /metrics scrape must still succeed (same contract as
            # LabeledCallbackGauge.samples)
            try:
                return [("", {}, float(self._fn()))]
            except Exception:
                return []
        if not self._values:
            return [("", {}, 0.0)] if not self.label_names else []
        return [("", dict(zip(self.label_names, k)), v)
                for k, v in sorted(self._values.items())]


class Histogram(_Metric):
    """Cumulative-bucket histogram, optionally labeled: with label_names
    set, each distinct labelset gets its own bucket/sum/count series
    (verify-pipeline latencies split by flush path / bucket rung).
    Unlabeled histograms expose a zeroed series before the first
    observation, matching the previous behavior."""

    kind = "histogram"

    def __init__(self, *args, buckets: tuple[float, ...] = _DEFAULT_BUCKETS,
                 label_names: tuple[str, ...] = (), **kw):
        super().__init__(*args, **kw)
        self.buckets = tuple(sorted(buckets))
        self.label_names = label_names
        # labelset key -> [per-bucket counts (+overflow), sum, n]
        self._series: dict[tuple, list] = {}
        if not label_names:
            self._series[()] = [[0] * (len(self.buckets) + 1), 0.0, 0]

    def observe(self, value: float, **labels) -> None:
        self.observe_n(value, 1, **labels)

    def observe_n(self, value: float, n: int, **labels) -> None:
        """`n` observations of the same value at once: the state `n`
        equal observes leave (a flush's rows all waited the same time)."""
        key = tuple(str(labels.get(name, "")) for name in self.label_names)
        cell = self._series.get(key)
        if cell is None:
            cell = self._series[key] = [[0] * (len(self.buckets) + 1), 0.0, 0]
        cell[1] += value * n
        cell[2] += n
        counts = cell[0]
        for i, b in enumerate(self.buckets):
            if value <= b:
                counts[i] += n
                return
        counts[-1] += n

    def label_stats(self) -> dict:
        """Per-labelset (count, sum) snapshot keyed by the label-value
        tuple — the read-side accessor derived views use (costmodel's
        achieved-FLOPs/s needs the device-execute mean per rung without
        re-parsing exposition text)."""
        return {key: (cell[2], cell[1]) for key, cell in self._series.items()}

    def samples(self):
        out = []
        for key in sorted(self._series):
            counts, total, n = self._series[key]
            lbl = dict(zip(self.label_names, key))
            cum = 0
            for b, c in zip(self.buckets, counts):
                cum += c
                out.append(("_bucket", {**lbl, "le": _fmt_value(float(b))},
                            float(cum)))
            cum += counts[-1]
            out.append(("_bucket", {**lbl, "le": "+Inf"}, float(cum)))
            out.append(("_sum", dict(lbl), total))
            out.append(("_count", dict(lbl), float(n)))
        return out


class LabeledCallbackGauge(_Metric):
    """Metric whose labeled samples come from a callback evaluated at
    scrape time: fn() -> list[(labels_dict, value)].  kind defaults to
    gauge; pass kind="counter" for monotonically increasing *_total
    series so the exposition type matches."""

    kind = "gauge"

    def __init__(self, *args, fn: Callable[[], list] = None,
                 kind: str = "gauge", **kw):
        super().__init__(*args, **kw)
        self._fn = fn
        self.kind = kind

    def samples(self):
        try:
            return [("", labels, float(v)) for labels, v in self._fn()]
        except Exception:
            return []


class CallbackCounter(LabeledCallbackGauge):
    """Scalar monotonic counter sampled from a callback at scrape time:
    *_total series whose value lives in application state (the verify
    service's counters) expose `# TYPE ... counter` instead of
    masquerading as gauges.  Reuses LabeledCallbackGauge's kind=
    mechanism and its omit-on-error sampling."""

    def __init__(self, *args, fn: Callable[[], float] = None, **kw):
        super().__init__(*args, kind="counter",
                         fn=(lambda: [({}, fn())]), **kw)


class Registry:
    def __init__(self) -> None:
        self._metrics: list[_Metric] = []

    def register(self, metric: _Metric) -> _Metric:
        self._metrics.append(metric)
        return metric

    def expose(self) -> str:
        return "\n".join(m.expose() for m in self._metrics) + "\n"


class MetricsServer:
    """GET /metrics on the instrumentation address."""

    def __init__(self, registry: Registry):
        from tendermint_tpu.utils.httpserv import TextHTTPServer

        self.registry = registry
        self._http = TextHTTPServer(self._route)

    async def start(self, host: str, port: int) -> tuple[str, int]:
        return await self._http.start(host, port)

    async def stop(self) -> None:
        await self._http.stop()

    async def _route(self, path: str):
        if path.startswith("/metrics"):
            return 200, "text/plain; version=0.0.4", self.registry.expose().encode()
        return 404, "text/plain", b"see /metrics\n"


def timer() -> float:
    return time.perf_counter()
