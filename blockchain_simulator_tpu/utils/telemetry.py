"""Fleet-wide host-side telemetry: tracing, metrics, flight recorder.

PR 2's observability layer records what a *simulation* did (probe series,
manifests, health verdicts).  This module records what the *system around
the simulations* did — the serving fleet, the batcher, the sweep tier —
as host-side primitives every tier shares:

- **Request-scoped tracing.**  A :class:`TraceContext` (``trace_id`` +
  ``span_id``) is minted at router admission (serve/router.py) and at
  sweep-chunk dispatch (parallel/sweep.py), propagated across processes
  via the ``X-Blocksim-Trace`` HTTP header, and every closed span becomes
  one JSON record: into the in-process flight recorder always, and into
  the span log (``$BLOCKSIM_SPANS_JSONL``, the shared rotating
  utils/obs.py writer) when armed.  The serving span model (README
  "Telemetry"): ``router.request`` → ``router.send`` → ``serve.request``
  → {``serve.admit``, ``serve.queue_wait``, ``serve.batch_wait``,
  ``serve.dispatch`` (pad-bucket attrs; children ``serve.dispatch.
  operands`` / ``.execute`` / ``.readback``), ``serve.answer``} —
  segments tile the request's wall clock, so a span tree accounts for the
  whole p50 by construction.
- **The same spans on the profiler's clock.**  This module is the ONE span
  source: every :func:`span` also opens a ``jax.profiler.TraceAnnotation``
  of the same name (its *twin*, carrying the span id), so inside any
  profiler session (``cli --profile``, ``jax.profiler.start_trace``, the
  benchmark's ``--trace 1``) the program's spans sit on the device
  trace's timeline by name; with no session the twin is a no-op.  A record
  and its twin come from one enter/exit, so any pair gives the offset
  between ``time.monotonic()`` and the trace clock
  (:func:`trace_clock_offset_ns`), which lays the spans synthesized at
  answer time by :func:`emit` on that timeline too (:func:`on_trace_clock`).
  ``span(..., record=False)`` is the twin alone: the batcher's thread
  states (``serve.batcher.idle`` / ``.hold`` / ``.flush``) turn over every
  few milliseconds and must not evict the flight ring's post-mortem.
- **Metrics registry.**  Cheap thread-safe counters / fixed-bucket
  histograms (:data:`metrics`), exposed as Prometheus text
  (``GET /metrics`` on the serve daemon and the fleet router) and as a
  compact snapshot on the run manifest (utils/obs.py).  Histogram
  percentiles power the ``/stats`` ``latency_ms`` blocks
  (serve/server.py, serve/router.py).
- **Flight recorder.**  A bounded in-memory ring of recent spans/events
  (:data:`flight`), dumped atomically to an ``ARTIFACT``-style JSON on
  shutdown, crash, supervisor degrade, or chaos invariant violation —
  when ``$BLOCKSIM_FLIGHT_DIR`` names a directory (unset = ring only,
  no file I/O).

HARD RULE (the host-sync-in-traced rule's telemetry corollary, enforced
by tests/test_zztelemetry.py): every call into this module is host-side
only.  Spans and counters must never appear inside jitted/vmapped/scanned
code — a span's ``time`` calls are host syncs.  Traced code names its own
work with ``jax.named_scope`` directly (models/pbft.py, models/
pbft_round.py, ops/scopes.py: HLO metadata, read from the same profiler
trace) and has utils/trace.py probe series.  Models and ops never import
this module; this module never imports jax (a router process stays
jax-free: the twin is found through ``sys.modules``).

Telemetry must never take down the thing it observes: every file write
is swallowed on failure, and :func:`FlightRecorder.dump` with no armed
directory is a no-op returning ``None``.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import statistics
import sys
import threading
import time
import uuid

from blockchain_simulator_tpu.utils import obs

# HTTP propagation header: "<trace_id>:<span_id>" (the sender's span
# becomes the receiver's parent).
TRACE_HEADER = "X-Blocksim-Trace"

# Span log path (JSONL via the rotating obs.append_jsonl writer); unset =
# spans stay in the flight-recorder ring only.
SPANS_ENV = "BLOCKSIM_SPANS_JSONL"

# Flight-recorder dump directory; unset = dumps are no-ops.
FLIGHT_ENV = "BLOCKSIM_FLIGHT_DIR"
# retention: newest K ARTIFACT_flight_*.json kept per dump directory
# (default 32; 0 disables pruning) — the flight-dir analog of the
# obs.append_jsonl size-capped rotation: post-mortems are rolling
# observability artifacts, and a long chaos drill or a violation storm
# must not fill the disk with them
FLIGHT_KEEP_ENV = "BLOCKSIM_FLIGHT_KEEP"
FLIGHT_KEEP_DEFAULT = 32

TELEMETRY_SCHEMA = 1

# monotonic -> wall mapping, fixed at import: code paths stamp
# time.monotonic() (the clock the serving stack already uses) and spans
# publish wall-clock starts so cross-process timelines align.
_EPOCH = time.time() - time.monotonic()


def new_trace_id() -> str:
    """16 hex chars, unique per admission/chunk (uuid4-derived)."""
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    """8 hex chars, unique within a trace."""
    return uuid.uuid4().hex[:8]


class TraceContext:
    """One (trace_id, span_id) point in a trace: the identity a child
    span parents to, and the value the ``X-Blocksim-Trace`` header
    carries across processes."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = str(trace_id)
        self.span_id = str(span_id)

    def header(self) -> str:
        return f"{self.trace_id}:{self.span_id}"

    def __eq__(self, other):
        return (isinstance(other, TraceContext)
                and other.trace_id == self.trace_id
                and other.span_id == self.span_id)

    def __repr__(self):
        return f"TraceContext({self.trace_id}:{self.span_id})"


def parse_header(value) -> TraceContext | None:
    """Parse a ``X-Blocksim-Trace`` header value; garbage (missing,
    malformed, empty ids) reads as None — a bad header must never reject
    a request."""
    if not isinstance(value, str) or ":" not in value:
        return None
    tid, _, sid = value.partition(":")
    tid, sid = tid.strip(), sid.strip()
    if not tid or not sid or not all(
            c in "0123456789abcdef" for c in (tid + sid).lower()):
        return None
    return TraceContext(tid, sid)


# ------------------------------------------------------------ span sinks ---

_tls = threading.local()
# extra span sinks (callables taking one span record): tests and the
# report tool install capture buffers here; the flight recorder is NOT a
# sink — it is unconditional.
_sinks: list = []
_sinks_lock = threading.Lock()


def current() -> TraceContext | None:
    """The calling thread's active trace context (set by :func:`span` /
    :func:`context`), or None."""
    return getattr(_tls, "ctx", None)


@contextlib.contextmanager
def context(ctx: TraceContext | None):
    """Install ``ctx`` as the thread's current trace context without
    opening a span — the HTTP handlers' header-extraction shim."""
    prev = current()
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


@contextlib.contextmanager
def capture():
    """Collect every span emitted (process-wide) during the block —
    drills and tests read the list after."""
    buf: list[dict] = []
    with _sinks_lock:
        _sinks.append(buf.append)
    try:
        yield buf
    finally:
        with _sinks_lock:
            try:
                _sinks.remove(buf.append)
            except ValueError:
                pass


def emit(name: str, t0: float, t1: float | None = None,
         trace: str | None = None, parent: str | None = None,
         span_id: str | None = None, status: str = "ok", sink=None,
         **attrs) -> str:
    """Record one closed span from explicit ``time.monotonic()`` stamps —
    the request-lifecycle synthesizer (serve/server.py builds a request's
    whole segment tree at answer time from stamps, because the segments
    straddle threads).  Returns the span id so callers can parent
    children to it.  Emission goes to the flight-recorder ring, any
    installed capture sinks, ``sink`` (this one record's own: the build
    log of utils/aotcache.py) and the span log when armed."""
    t1 = time.monotonic() if t1 is None else t1
    sid = span_id or new_span_id()
    rec = {
        "kind": "span",
        "name": str(name),
        "trace": trace or new_trace_id(),
        "id": sid,
        "parent": parent,
        "ts": round(t0 + _EPOCH, 6),
        "dur_ms": round(max(t1 - t0, 0.0) * 1000.0, 3),
        "pid": os.getpid(),
        "status": str(status),
    }
    if attrs:
        rec["attrs"] = {k: v for k, v in attrs.items() if v is not None}
    flight.record(rec)
    with _sinks_lock:
        sinks = list(_sinks)
    if sink is not None:
        sinks.append(sink)
    for each in sinks:
        try:
            each(rec)
        except Exception:
            pass  # a broken sink must never break the emitting code path
    path = os.environ.get(SPANS_ENV)
    if path:
        obs.append_jsonl(rec, path)
    return sid


def _twin(name: str, attrs: dict):
    """The profiler twin of a span, as a context manager: a
    ``jax.profiler.TraceAnnotation`` named like the span, its attrs as the
    event's stats.  jax is looked up, never imported (a process that has not
    imported jax — the fleet router — has no profiler to annotate); outside
    a profiler session the annotation is a no-op."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    try:
        return jax.profiler.TraceAnnotation(
            name, **{k: v for k, v in attrs.items() if v is not None})
    except Exception:  # a failing profiler must never break the spanned code
        return contextlib.nullcontext()


@contextlib.contextmanager
def span(name: str, ctx: TraceContext | None = None, record: bool = True,
         sink=None, **attrs):
    """Open/close one span around a block: child of ``ctx`` (or the
    thread's current context; a fresh trace when neither exists), set as
    the thread's current context inside the block — so nested spans and
    outbound HTTP headers (serve/router.py ``_http``) pick it up.  An
    escaping exception marks ``status="error"`` and re-raises.  Yields
    the span's own :class:`TraceContext`; ``sink`` is :func:`emit`'s.

    Every span has a twin in the profiler's trace (:func:`_twin`) that
    carries ``<trace id>:<span id>`` as its ``span`` stat (the header form:
    never all digits, so the profiler keeps it a string).  ``record=False``
    is the twin ALONE — no record, no flight-ring entry, no context change,
    yields None — for thread states that turn over every few milliseconds."""
    if not record:
        with _twin(name, attrs):
            yield None
        return
    parent = ctx if ctx is not None else current()
    tid = parent.trace_id if parent is not None else new_trace_id()
    sid = new_span_id()
    mine = TraceContext(tid, sid)
    prev = current()
    _tls.ctx = mine
    status = "ok"
    t0 = t1 = time.monotonic()
    try:
        with _twin(name, {"span": mine.header(), **attrs}):
            t0 = time.monotonic()
            try:
                yield mine
            finally:
                t1 = time.monotonic()
    except BaseException:
        status = "error"
        raise
    finally:
        _tls.ctx = prev
        emit(name, t0, t1, trace=tid,
             parent=parent.span_id if parent is not None else None,
             span_id=sid, status=status, sink=sink, **attrs)


def trace_clock_offset_ns(records, twins: dict) -> float | None:
    """The nanoseconds to add to a span record's ``ts`` (seconds, this
    process's wall-anchored monotonic clock) to land on a profiler trace's
    clock.  ``records`` are span records (a :func:`capture` buffer, the
    flight ring, the span log); ``twins`` maps span id → the start, in the
    trace's nanoseconds, of the annotation that carries it as its ``span``
    stat.  Median over every pair found; None without one."""
    diffs = [twins[r["id"]] - r["ts"] * 1e9 for r in records
             if r.get("id") in twins]
    return statistics.median(diffs) if diffs else None


def on_trace_clock(rec: dict, offset_ns: float) -> tuple[float, float]:
    """``(start_ns, end_ns)`` of a span record on the trace's clock: what
    places the segments :func:`emit` synthesizes at answer time
    (``serve.queue_wait``, ``serve.batch_wait``; they have no twin) on the
    device trace's timeline."""
    start = rec["ts"] * 1e9 + offset_ns
    return start, start + rec["dur_ms"] * 1e6


def on_monotonic_clock(rec: dict) -> tuple[float, float]:
    """``(t0, t1)`` of a span record on THIS process's ``time.monotonic()``
    clock, the clock :func:`emit` took them on: what compares a record with
    a stamp the caller took itself (the benchmark's window start)."""
    t0 = rec["ts"] - _EPOCH
    return t0, t0 + rec["dur_ms"] / 1000.0


# --------------------------------------------------------------- metrics ---

# Fixed latency buckets (ms): wide enough for a sub-ms solo dispatch and
# a multi-second cold compile; fixed so two processes' histograms merge.
DEFAULT_MS_BUCKETS = (
    1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
)


class Counter:
    """Monotone counter.  All mutation under the registry lock the
    instrument was created with (instrument methods are the hot path:
    one lock, one add)."""

    __slots__ = ("name", "labels", "_lock", "value")

    def __init__(self, name: str, labels: dict, lock: threading.Lock):
        self.name = name
        self.labels = labels
        self._lock = lock
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n


class Histogram:
    """Fixed-bucket histogram (cumulative exposition, Prometheus-style).

    ``bounds`` are upper bucket edges; an implicit +Inf bucket catches
    the tail.  :meth:`percentile` answers at bucket resolution — the
    upper edge of the bucket the nearest-rank observation fell in,
    capped at the maximum observed value (so the +Inf bucket reports a
    real number).  Good enough for the ``/stats`` p50/p95/p99 blocks;
    exact percentiles stay obs.percentile over raw samples where callers
    keep them (tools/serve_bench.py)."""

    __slots__ = ("name", "labels", "bounds", "_lock", "counts", "sum",
                 "count", "_max")

    def __init__(self, name: str, labels: dict, lock: threading.Lock,
                 bounds=DEFAULT_MS_BUCKETS):
        self.name = name
        self.labels = labels
        self.bounds = tuple(float(b) for b in bounds)
        if list(self.bounds) != sorted(self.bounds):
            raise ValueError("histogram bounds must be sorted ascending")
        self._lock = lock
        self.counts = [0] * (len(self.bounds) + 1)  # [+Inf] last
        self.sum = 0.0
        self.count = 0
        self._max = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        i = 0
        for b in self.bounds:
            if v <= b:
                break
            i += 1
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1
            if v > self._max:
                self._max = v

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile at bucket resolution (0.0 when
        empty)."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
            vmax = self._max
        if total == 0:
            return 0.0
        rank = max(1, int(round(q / 100.0 * total)))
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank:
                edge = self.bounds[i] if i < len(self.bounds) else vmax
                return round(min(edge, vmax), 3)
        return round(vmax, 3)

    def percentiles(self, qs=(50.0, 95.0, 99.0)) -> dict:
        return {f"p{int(q)}": self.percentile(q) for q in qs}


def _label_str(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return "{" + inner + "}"


class MetricsRegistry:
    """Get-or-create registry of instruments keyed on (name, labels).

    One process-global instance (:data:`metrics`) backs ``/metrics`` on
    every HTTP surface; tests and per-server ``/stats`` percentiles use
    private :class:`Histogram` instances instead, so N servers in one
    process do not blur each other's latency."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: dict = {}

    def _get(self, cls, name: str, labels: dict, **kw):
        key = (cls.__name__, name, tuple(sorted(labels.items())))
        with self._lock:
            inst = self._instruments.get(key)
            if inst is None:
                inst = cls(name, dict(labels), self._lock, **kw)
                self._instruments[key] = inst
            return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def histogram(self, name: str, bounds=DEFAULT_MS_BUCKETS,
                  **labels) -> Histogram:
        return self._get(Histogram, name, labels, bounds=bounds)

    def reset(self) -> None:
        """Drop every instrument — scenario/test isolation (the drills
        bracket runs with snapshots instead; see chaos/invariants.py
        check_telemetry)."""
        with self._lock:
            self._instruments = {}

    # ------------------------------------------------------- exposition ---
    def exposition(self) -> str:
        """Prometheus text format v0.0.4 — the ``GET /metrics`` body."""
        lines: list[str] = []
        with self._lock:
            instruments = list(self._instruments.values())
        typed: set[str] = set()
        for inst in sorted(instruments, key=lambda i: i.name):
            kind = type(inst).__name__.lower()
            if inst.name not in typed:
                lines.append(f"# TYPE {inst.name} {kind}")
                typed.add(inst.name)
            ls = _label_str(inst.labels)
            if isinstance(inst, Histogram):
                cum = 0
                for b, c in zip(inst.bounds, inst.counts):
                    cum += c
                    lb = dict(inst.labels, le=f"{b:g}")
                    lines.append(f"{inst.name}_bucket{_label_str(lb)} {cum}")
                cum += inst.counts[-1]
                lb = dict(inst.labels, le="+Inf")
                lines.append(f"{inst.name}_bucket{_label_str(lb)} {cum}")
                lines.append(f"{inst.name}_sum{ls} {inst.sum:g}")
                lines.append(f"{inst.name}_count{ls} {inst.count}")
            else:
                lines.append(f"{inst.name}{ls} {inst.value:g}")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """Compact JSON-able view: counters by ``name{labels}``,
        histograms as {count, sum, p50, p95, p99} — the flight-recorder
        dump and ARTIFACT_telemetry.json payload, and the delta source
        for chaos/invariants.check_telemetry."""
        out: dict = {"counters": {}, "histograms": {}}
        with self._lock:
            instruments = list(self._instruments.values())
        for inst in instruments:
            key = inst.name + _label_str(inst.labels)
            if isinstance(inst, Counter):
                out["counters"][key] = inst.value
            else:
                out["histograms"][key] = {
                    "count": inst.count, "sum": round(inst.sum, 3),
                    **inst.percentiles(),
                }
        return out

    def manifest(self) -> dict:
        """The tiny provenance block obs.manifest attaches to runs.jsonl
        lines when telemetry has instruments: counter totals only (the
        full snapshot would bloat every access-log line)."""
        with self._lock:
            instruments = list(self._instruments.values())
        counters = {
            inst.name + _label_str(inst.labels): inst.value
            for inst in instruments if isinstance(inst, Counter)
        }
        return {"counters": counters, "spans": flight.spans_recorded}


metrics = MetricsRegistry()


def write_exposition(handler) -> None:
    """Serve the ``GET /metrics`` body on a BaseHTTPRequestHandler — the
    one Prometheus endpoint implementation both HTTP surfaces share
    (serve/__main__.py daemon, serve/router.py fleet front)."""
    blob = metrics.exposition().encode()
    handler.send_response(200)
    handler.send_header("Content-Type", "text/plain; version=0.0.4")
    handler.send_header("Content-Length", str(len(blob)))
    handler.end_headers()
    handler.wfile.write(blob)


# -------------------------------------------------------- flight recorder ---


class FlightRecorder:
    """Bounded ring of the most recent spans/events in this process.

    Always on (a ring append is two list ops under a lock); the *file*
    side is armed by ``$BLOCKSIM_FLIGHT_DIR`` — :meth:`dump` writes one
    atomic ``ARTIFACT``-style JSON (tmp + ``os.replace``) named after its
    trigger, so a crash, a chaos invariant violation, a supervisor
    degrade, or a shutdown each leave a self-describing post-mortem.
    Dump failures are swallowed: the recorder must never take down the
    process it is recording."""

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: list[dict] = []
        self._next = 0
        self.spans_recorded = 0
        self.dumps = 0
        self._dump_seq = itertools.count(1)

    def record(self, rec: dict) -> None:
        with self._lock:
            if len(self._ring) < self.capacity:
                self._ring.append(rec)
            else:
                self._ring[self._next % self.capacity] = rec
            self._next += 1
            if rec.get("kind") == "span":
                self.spans_recorded += 1

    def note(self, event: str, **fields) -> None:
        """Record one non-span event (supervisor transitions, chaos
        verdicts, lifecycle marks)."""
        self.record({"kind": "event", "event": str(event),
                     "ts": round(time.time(), 6), "pid": os.getpid(),
                     **fields})

    def snapshot(self) -> list[dict]:
        """Ring contents, oldest first."""
        with self._lock:
            if len(self._ring) < self.capacity:
                return list(self._ring)
            i = self._next % self.capacity
            return self._ring[i:] + self._ring[:i]

    def reset(self) -> None:
        with self._lock:
            self._ring = []
            self._next = 0
            self.spans_recorded = 0

    def dump(self, reason: str, path: str | None = None) -> str | None:
        """Write the post-mortem; returns the path, or None when neither
        ``path`` nor ``$BLOCKSIM_FLIGHT_DIR`` is set (disarmed) or the
        write failed (swallowed)."""
        if path is None:
            d = os.environ.get(FLIGHT_ENV)
            if not d:
                return None
            # sequence number: repeated same-reason triggers in one
            # process (a drill's scenarios, a long sweep's degrades)
            # each keep their own post-mortem instead of overwriting
            path = os.path.join(
                d, f"ARTIFACT_flight_{reason}_{os.getpid()}"
                   f"_{next(self._dump_seq)}.json")
        doc = {
            "telemetry_schema": TELEMETRY_SCHEMA,
            "reason": str(reason),
            "ts": round(time.time(), 3),
            "pid": os.getpid(),
            "records": self.snapshot(),
            "metrics": metrics.snapshot(),
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(doc, f, default=str)
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        self._prune(os.path.dirname(path) or ".")
        with self._lock:
            self.dumps += 1
        return path

    @staticmethod
    def _prune(d: str) -> None:
        """Keep only the newest ``$BLOCKSIM_FLIGHT_KEEP`` (default 32)
        ``ARTIFACT_flight_*.json`` post-mortems in ``d``; 0 disables.
        Runs after every successful dump; failures are swallowed like the
        dump's own (the recorder never takes down its process)."""
        try:
            keep = int(os.environ.get(FLIGHT_KEEP_ENV, FLIGHT_KEEP_DEFAULT))
        except ValueError:
            keep = FLIGHT_KEEP_DEFAULT
        if keep <= 0:
            return
        try:
            names = [n for n in os.listdir(d)
                     if n.startswith("ARTIFACT_flight_")
                     and n.endswith(".json")]
            if len(names) <= keep:
                return
            paths = [os.path.join(d, n) for n in names]
            # (mtime, name): stable order for same-second bursts
            paths.sort(key=lambda p: (os.path.getmtime(p), p))
            for p in paths[:-keep]:
                os.unlink(p)
        except OSError:
            pass


flight = FlightRecorder()


def install_crash_dump() -> None:
    """Chain a flight-recorder dump onto ``sys.excepthook`` AND
    ``threading.excepthook`` — the daemon entrypoints call this once so
    an unhandled exception leaves a post-mortem before the traceback.
    The threading hook matters more: the daemons' crash surface is
    worker threads (HTTP handlers, router dispatch/hedge/handoff), not
    the main thread blocking in serve_forever.  (kill -9 has no hook;
    the WAL and sweep journal carry that case.)"""
    import sys
    import threading as _threading

    prev = sys.excepthook

    def hook(exc_type, exc, tb):
        try:
            flight.note("crash", error=f"{exc_type.__name__}: {exc}"[:500])
            flight.dump("crash")
        finally:
            prev(exc_type, exc, tb)

    sys.excepthook = hook
    prev_t = _threading.excepthook

    def thread_hook(args):
        try:
            flight.note(
                "crash",
                thread=getattr(args.thread, "name", None),
                error=f"{args.exc_type.__name__}: {args.exc_value}"[:500],
            )
            flight.dump("crash")
        finally:
            prev_t(args)

    _threading.excepthook = thread_hook


# ---- Raft with terms (SimConfig.raft_terms): what the groups of a run went
# through, as counters, made where ``committee.*`` are: host side, where the
# metrics dicts are computed (topo/committee.metrics for a stack of groups,
# runner.run_simulation for a flat run; models/ stays free of this module)
# each counter with the key of a group's metrics dict it sums
# (``raft.groups``: the groups themselves)
_RAFT_COUNTED = {
    "raft.groups": None, "raft.term_bumps": "term_final",
    "raft.step_downs": "step_downs", "raft.term_conflicts": "term_conflicts",
    # under a crash schedule (FaultConfig.crashes)
    "raft.crashes": "crashes", "raft.restarts": "restarts",
    "raft.failovers": "failovers",
    "raft.crashes_no_leader": "crashes_found_no_leader",
}
RAFT_COUNTERS = tuple(_RAFT_COUNTED)


def count_raft_groups(groups) -> None:
    """Add the Raft metrics dicts ``groups`` (one a group) that ran with
    terms to :data:`RAFT_COUNTERS`: groups read, and over them the sums of
    ``term_final``, ``step_downs`` and ``term_conflicts``, and of
    ``crashes``, ``restarts``, ``failovers`` and ``crashes_found_no_leader``
    where they ran under a crash schedule.  A dict without terms counts
    nothing."""
    with_terms = [g for g in groups if "term_final" in g]
    if not with_terms:
        return
    for name, key in _RAFT_COUNTED.items():
        if key is None:
            metrics.counter(name).inc(len(with_terms))
        elif key in with_terms[0]:
            metrics.counter(name).inc(sum(g[key] for g in with_terms))


# ---- the ring ops' lane rule (ops/ring.node_minor): ring values pinned
# slot-major, lane-minor while programs were traced.  Traced code never
# calls this module: ops/ring.py counts in plain Python where it is traced
# and the program builders move the count here when a trace closes
# (utils/aotcache.BuildLog.stage_closed)
RING_COUNTER = "ring.lane_pinned"


# ---- link classes (ops/linkclass.py): what the classed programs traced so
# far hold, one increment a traced program (``linkclass.programs``) and over
# them the sums of their classes, the distinct offsets of their one-way delay
# lines, their ring depths and the bytes of state one lane carries.  Counted
# in plain Python where ``models/pbft.init`` is traced (ops/linkclass.traced)
# and moved here when a trace closes, as the ring counter above is
LINKCLASS_COUNTERS = ("linkclass.programs", "linkclass.classes",
                      "linkclass.offsets", "linkclass.ring_depth",
                      "linkclass.lane_state_bytes")
