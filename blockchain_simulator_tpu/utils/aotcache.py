"""Executable registry, AOT staging and jax's compile cache.

Compilation dominates end-to-end wall on every sweep-shaped workload this
repo cares about: a 0..33 Byzantine f-sweep used to pay one full XLA compile
PER FAULT LEVEL for seconds of actual simulation.  This module is the one
place compiled programs live:

- **In-process registry** (:class:`ExecutableRegistry`, module singleton
  :data:`registry`): a single keyed LRU store that subsumes the scattered
  ``functools.lru_cache`` factories (``runner.make_sim_fn``,
  ``utils/trace.py``'s traced fns, ``parallel/sweep._batched_fn``).  Factory
  functions opt in with :func:`cached_factory` — the jaxlint
  ``static-arg-recompile-hazard`` rule recognizes it as a sanctioned cache
  decorator, same as ``functools.lru_cache``.  Hit/miss/eviction stats are
  exported into every run manifest (``utils/obs.py`` ``cache`` block).
- **AOT staging** (:func:`aot_compile`, memoized by :func:`aot_cached`):
  explicit ``jit(f).lower(*args).compile()`` with the executable's own cost
  analysis attached — the compile-vs-run split bench.py wants, without a
  throwaway first execution.
- **The build log** (:class:`BuildLog`, ``registry.builds()``): what every
  build cost and where it went.  jax publishes each trace, lowering and
  backend compile (a load from the persistent cache included) through
  ``jax.monitoring``; one set of listeners turns each into a ``build.trace``
  / ``build.lower`` / ``build.compile`` record of utils/telemetry.py with
  the program's name on it, a registry miss adds ``build.factory``, and
  ``registry.warming()`` marks what a server compiles after it prewarmed
  (``late``).  Records, not dispatches: nothing here runs on a hit.
- **The one persistent cache is jax's own** (:func:`enable_xla_cache`),
  turned on by every entry point (cli, serve, bench.py, the benchmark,
  chip_smoke.py's children): where ``$JAX_COMPILATION_CACHE_DIR`` says
  when it is set, else at the fixed ``<repo>/.jax_cache``.  Every
  ``.lower().compile()`` passes through it, ``aot_compile``'s included; an
  unreadable entry is jax's to handle (it warns and compiles,
  tests/test_zsweep_cache.py).

Design constraint: **never touch a backend at import** (jaxlint
module-scope-backend-touch).  A chip belongs to one process at a time, so a
parent that touches jax at import cannot launch chip children.  This module
does not even import jax at module scope — ``utils/obs.py`` imports it from
jax-free parents.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
import sys
import threading
import time

# jax's own compilation cache is placed from OUTSIDE with jax's own variable
# (jax reads it itself — this module then sets no directory in code);
# unset, every entry point shares one fixed path inside the checkout.  The
# directory must not move between runs: a cache that moves never hits.
XLA_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_XLA_CACHE = os.path.join(_REPO, ".jax_cache")


def _mesh_desc(args: tuple, kwargs: tuple) -> str | None:
    """Compact mesh descriptor (``"sweep=8,nodes=1"``) of the first
    ``jax.sharding.Mesh`` among a registry key's arguments, or None for a
    single-device entry.  Duck-typed (``axis_names`` + ``devices`` +
    mapping ``shape``) — this module never imports jax (module
    docstring: a stats read must not be able to init a backend)."""
    for a in args + tuple(v for _, v in kwargs):
        if hasattr(a, "axis_names") and hasattr(a, "devices"):
            try:
                return ",".join(
                    f"{k}={int(v)}" for k, v in dict(a.shape).items()
                )
            except Exception:
                return None
    return None


def _display_key(name: str, args: tuple, kwargs: tuple) -> str:
    """Short human-readable key for stats/manifests: the factory name plus
    the config hash of the first dataclass argument (the join key used
    everywhere else in the observability layer)."""
    import dataclasses

    from blockchain_simulator_tpu.utils import obs

    for a in args + tuple(v for _, v in kwargs):
        if dataclasses.is_dataclass(a):
            return f"{name}:{obs.config_hash(a)}"
    return name


# jax's three stage spans (jax.monitoring time spans, each with the
# function's or module's name as ``fun_name``) -> the record each becomes
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "build.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "build.lower",
    "/jax/core/compile/backend_compile_duration": "build.compile",
}
# the persistent cache's events, all fired inside the backend-compile span
# of the thread that compiles
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
BUILD_LOG_SIZE = 1024
# a trace or lowering shorter than this is not written.  A program's trace
# holds hundreds of jnp functions traced inside it for tens of microseconds
# each, and jax reports a "trace" of that length on every call of a jitted
# function with tracers even when its own cache answers (``jax.random.key``
# of a seed array, once per served request): the flight ring is for
# post-mortems.  A first trace of anything takes longer.
FLOOR_S = 0.001


class _OpenBuild:
    """A build that has started and not ended, on its thread's stack."""

    __slots__ = ("name", "sid", "trace", "parent", "root")

    def __init__(self, name, sid, trace, parent, root):
        self.name, self.sid, self.trace = name, sid, trace
        self.parent, self.root = parent, root


class BuildLog:
    """What this process built, when, and for how long: a bounded log of
    ``build.*`` span records (utils/telemetry.py's own: each goes through
    ``emit`` / ``span`` and lands here as that call's ``sink``) and the
    counters ``stats_snapshot()["builds"]`` shows.

    ``build.factory`` (attrs ``factory``, ``key``) is a registry miss: the
    host-side construction before jax sees anything.  ``build.trace`` /
    ``build.lower`` / ``build.compile`` (attrs ``fun``: jax's name of the
    function or module; ``thread``: the building thread's name) are jax's
    stages, one record each, stamped by jax and moved once to
    ``time.monotonic()``'s clock.  ``build.compile`` also says what the
    persistent cache did (``cache``: ``hit`` / ``miss`` / ``off``;
    ``retrieval_ms`` on a hit) and whether the server had finished warming
    (``late``).  A record opened
    while another build record was open on its thread (a jitted product
    traced inside an outer trace, a lowering rule that traces, a factory
    called by a factory, an AOT compile inside a factory) carries that
    record's id as ``parent`` and is left out of the sums, so the sums of
    one thread never count a moment twice.  Every other record is a root.
    A trace or lowering shorter than :data:`FLOOR_S` is not written."""

    def __init__(self, size: int = BUILD_LOG_SIZE):
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(maxlen=size)
        self._tls = threading.local()
        self.n = 0
        self._seconds = dict.fromkeys(
            ("build.factory", *_STAGES.values()), 0.0)
        self.cache_hits = 0
        self.cache_misses = 0
        self.retrieval_s = 0.0
        self.late = 0
        self._last_late: dict | None = None
        self._warming = 0
        self._warmed = False
        self._lane_pinned = 0  # of ops/ring.lane_pinned, moved so far
        self._linkclass = {}  # of ops/linkclass.traced, moved so far

    # ----------------------------------------------------------- reading ---
    def records(self) -> list[dict]:
        with self._lock:
            return list(self._records)

    def snapshot(self) -> dict:
        """Pure counter reads.  The sums are over root records."""
        with self._lock:
            return {
                "n": self.n,
                "factory_s": round(self._seconds["build.factory"], 6),
                "trace_s": round(self._seconds["build.trace"], 6),
                "lower_s": round(self._seconds["build.lower"], 6),
                "compile_s": round(self._seconds["build.compile"], 6),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "retrieval_s": round(self.retrieval_s, 6),
                "late": self.late,
                "last_late": self._last_late,
                "last": self._records[-1] if self._records else None,
            }

    @contextlib.contextmanager
    def warming(self):
        """Process-wide, not per thread: a prewarm's compiles may run on
        another thread than the one that asked.  Blocks nest and repeat (the
        served benchmark prewarms twice); a process that never opens one
        has no late builds."""
        with self._lock:
            self._warming += 1
        try:
            yield
        finally:
            with self._lock:
                self._warming -= 1
                self._warmed = True

    # ----------------------------------------------------------- writing ---
    def _open(self, name: str) -> _OpenBuild:
        """Push an entry on this thread's stack of open builds.  Its ids are
        drawn at the start so that an inner record can name its parent
        before the parent closes; a root hangs under the thread's telemetry
        context, if it has one (the ``serve.dispatch.execute`` of the
        request that made a server compile)."""
        from blockchain_simulator_tpu.utils import telemetry

        stack = self._tls.__dict__.setdefault("open", [])
        if stack:
            entry = _OpenBuild(name, telemetry.new_span_id(), stack[-1].trace,
                               stack[-1].sid, False)
        else:
            ctx = telemetry.current()
            entry = _OpenBuild(
                name, telemetry.new_span_id(),
                ctx.trace_id if ctx else telemetry.new_trace_id(),
                ctx.span_id if ctx else None, True)
        stack.append(entry)
        return entry

    def _close(self, name: str) -> _OpenBuild:
        stack = self._tls.__dict__.get("open", [])
        for i in range(len(stack) - 1, -1, -1):
            if stack[i].name == name:
                return stack.pop(i)
        # its start was not told: the listeners went in inside it (the first
        # registry miss of a process, under somebody's trace)
        return _OpenBuild(name, None, None, None, True)

    def _add(self, root: bool, rec: dict) -> None:
        attrs = rec.get("attrs") or {}
        with self._lock:
            self._records.append(rec)
            self.n += 1
            if root:
                self._seconds[rec["name"]] += rec["dur_ms"] / 1000.0
            if attrs.get("cache") == "hit":
                self.cache_hits += 1
                self.retrieval_s += attrs.get("retrieval_ms", 0.0) / 1000.0
            elif attrs.get("cache") == "miss":
                self.cache_misses += 1
            if attrs.get("late"):
                self.late += 1
                self._last_late = rec

    def factory(self, name: str, args: tuple, kwargs: tuple, build):
        """``build(*args, **kwargs)`` inside a ``build.factory`` span: the
        registry's miss path."""
        from blockchain_simulator_tpu.utils import telemetry

        _listen()
        entry = self._open("build.factory")
        ctx = (telemetry.TraceContext(entry.trace, entry.parent)
               if entry.parent is not None else None)
        try:
            with telemetry.span(
                    "build.factory", ctx=ctx,
                    sink=functools.partial(self._add, entry.root),
                    factory=name, key=_display_key(name, args, kwargs)) as me:
                entry.sid, entry.trace = me.span_id, me.trace_id
                return build(*args, **dict(kwargs))
        finally:
            self._close("build.factory")

    # jax.monitoring's listeners (:func:`_listen`), each on the building
    # thread: a stage's start arrives as a scalar, its end as a time span,
    # the persistent cache's verdict as events and a duration in between
    def stage_opened(self, event: str, _value=None, **_kw) -> None:
        name = _STAGES.get(event)
        if name is None:
            return
        self._open(name)
        if name == "build.compile":
            self._tls.cache, self._tls.retrieval_s = "off", None

    def cache_event(self, event: str, seconds: float | None = None,
                    **_kw) -> None:
        if event == _CACHE_ASKED:
            self._tls.cache = "miss"  # until the cache says it had it
        elif event == _CACHE_HIT:
            self._tls.cache = "hit"
        elif event == _CACHE_RETRIEVAL:
            self._tls.retrieval_s = seconds

    def stage_closed(self, event: str, start: float, end: float,
                     fun_name: str | None = None, **_kw) -> None:
        """One of jax's stage spans, ``time.time()`` stamps: emit it on
        ``time.monotonic()``'s clock (the offset is read now, at the span's
        end, so a wall clock set since import moves nothing)."""
        name = _STAGES.get(event)
        if name is None:
            return
        from blockchain_simulator_tpu.utils import telemetry

        shift = time.monotonic() - time.time()
        entry = self._close(name)
        if name == "build.trace":
            self._count_lane_pinned()
        if name != "build.compile" and end - start < FLOOR_S:
            return
        attrs = {"fun": fun_name, "thread": threading.current_thread().name}
        if name == "build.compile":
            attrs["cache"] = getattr(self._tls, "cache", "off")
            retrieval_s = getattr(self._tls, "retrieval_s", None)
            if attrs["cache"] == "hit" and retrieval_s is not None:
                attrs["retrieval_ms"] = round(retrieval_s * 1000.0, 3)
            with self._lock:
                late = self._warmed and not self._warming
            if late:
                attrs["late"] = True
                telemetry.flight.note("build.late", fun=fun_name,
                                      cache=attrs["cache"])
        telemetry.emit(name, start + shift, end + shift, trace=entry.trace,
                       parent=entry.parent, span_id=entry.sid,
                       sink=functools.partial(self._add, entry.root), **attrs)

    def _count_lane_pinned(self) -> None:
        """Move what the ring ops' lane rule pinned while programs were
        traced (ops/ring.lane_pinned, plain Python: traced code never calls
        utils/telemetry.py) to the ``ring.lane_pinned`` counter, and what
        the classed programs traced hold to the ``linkclass.*`` counters."""
        from blockchain_simulator_tpu.utils import telemetry

        ring = sys.modules.get("blockchain_simulator_tpu.ops.ring")
        if ring is None:
            return
        with self._lock:
            n = ring.lane_pinned[0] - self._lane_pinned
            self._lane_pinned += n
        if n:
            telemetry.metrics.counter(telemetry.RING_COUNTER).inc(n)
        lc = sys.modules.get("blockchain_simulator_tpu.ops.linkclass")
        if lc is None:
            return
        # likewise what the classed programs traced hold (ops/linkclass.traced
        # -> the ``linkclass.*`` counters)
        with self._lock:
            moved = {k: v - self._linkclass.get(k, 0)
                     for k, v in lc.traced.items()}
            self._linkclass = dict(lc.traced)
        for k, v in moved.items():
            if v:
                telemetry.metrics.counter(f"linkclass.{k}").inc(v)


class ExecutableRegistry:
    """Keyed LRU store for built callables/executables with hit/miss stats.

    Keys are ``(factory name, args, kwargs)`` — every factory argument in
    this repo is hashable (frozen ``SimConfig``, ``jax.sharding.Mesh``,
    ints), the same property the old per-module ``lru_cache``s relied on.
    """

    def __init__(self, maxsize: int = 128):
        self.maxsize = maxsize
        self._entries: collections.OrderedDict = collections.OrderedDict()
        # key -> mesh descriptor string (None for single-device entries);
        # kept in lockstep with _entries so stats can expose the mesh spec
        # of every live entry without re-parsing keys
        self._mesh: dict = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.last_key: str | None = None
        self.last_mesh: str | None = None
        self._builds = BuildLog()

    # ---------------------------------------------------------- memoize ---
    def get(self, name: str, args: tuple, kwargs: dict, build):
        """Return the cached build for ``(name, args, kwargs)``, building
        (and recording a miss) when absent.  LRU beyond ``maxsize``."""
        key = (name, args, tuple(sorted(kwargs.items())))
        with self._lock:
            if key in self._entries:
                self.hits += 1
                self._entries.move_to_end(key)
                self.last_key = _display_key(name, args, key[2])
                self.last_mesh = self._mesh.get(key)
                return self._entries[key]
        # build OUTSIDE the lock: builds trace/compile for minutes and must
        # not serialize unrelated factories behind a single mutex
        value = self._builds.factory(name, args, key[2], build)
        with self._lock:
            self.misses += 1
            self.last_key = _display_key(name, args, key[2])
            self.last_mesh = _mesh_desc(args, key[2])
            self._entries[key] = value
            self._entries.move_to_end(key)
            self._mesh[key] = self.last_mesh
            while len(self._entries) > self.maxsize:
                evicted, _ = self._entries.popitem(last=False)
                self._mesh.pop(evicted, None)
                self.evictions += 1
        return value

    def clear(self, name: str | None = None) -> None:
        """Drop every entry (``name=None``) or just one factory's entries —
        the ``lru_cache.cache_clear`` analog ``cached_factory`` wrappers
        expose (tools/ablate.py patches ops and rebuilds through a cleared
        ``make_sim_fn``; a shared-store clear must not evict every other
        factory with it)."""
        with self._lock:
            if name is None:
                self._entries.clear()
                self._mesh.clear()
                return
            for key in [k for k in self._entries if k[0] == name]:
                del self._entries[key]
                self._mesh.pop(key, None)

    def __len__(self) -> int:
        return len(self._entries)

    # ------------------------------------------------------------ stats ---
    def stats(self) -> dict:
        """Full stats snapshot (tests, artifacts)."""
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "entries": len(self._entries),
                "last_key": self.last_key,
            }

    def stats_snapshot(self) -> dict:
        """Thread-safe point-in-time snapshot for stats endpoints: the full
        :meth:`stats` record plus a per-factory entry breakdown.  The
        scenario server (serve/) attaches this to its ``/stats`` endpoint
        and its bench/self-test manifests so a running daemon's cache state
        is inspectable without touching jax (pure counter reads).

        Schema note (v. mesh bump): ``mesh`` maps each factory to a
        ``{mesh descriptor: entry count}`` breakdown — the mesh spec of
        every live registry entry (``"sweep=8,nodes=1"``; single-device
        entries count under ``"none"``).  ``builds`` is the build log's
        counters (:meth:`BuildLog.snapshot`).  Readers must tolerate absent
        or grown keys (the serve/ contract)."""
        with self._lock:
            by_factory: dict[str, int] = {}
            by_mesh: dict[str, dict[str, int]] = {}
            for key in self._entries:
                by_factory[key[0]] = by_factory.get(key[0], 0) + 1
                desc = self._mesh.get(key) or "none"
                fac = by_mesh.setdefault(key[0], {})
                fac[desc] = fac.get(desc, 0) + 1
            snap = self.stats()  # RLock: safe to re-enter
            snap["by_factory"] = dict(sorted(by_factory.items()))
            snap["mesh"] = {
                k: dict(sorted(v.items())) for k, v in sorted(by_mesh.items())
            }
        snap["builds"] = self._builds.snapshot()
        return snap

    # -------------------------------------------------------- build log ---
    def builds(self) -> list[dict]:
        """The ``build.*`` records of this process, oldest first, the
        newest :data:`BUILD_LOG_SIZE` of them (:class:`BuildLog`)."""
        return self._builds.records()

    def warming(self):
        """Context manager around a server's prewarm: builds inside it are
        expected, and from its first exit on every backend compile made
        outside one is ``late`` (:meth:`BuildLog.warming`)."""
        return self._builds.warming()

    def manifest(self) -> dict:
        """The compact ``cache`` block utils/obs.py attaches to every
        runs.jsonl line.  Pure counter reads — never touches jax."""
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "key": self.last_key,
                "mesh": self.last_mesh,
            }


registry = ExecutableRegistry()


def cached_factory(name: str):
    """Decorator: memoize a ``factory(*hashable_args) -> callable`` in the
    process-wide :data:`registry` (the ``functools.lru_cache`` replacement;
    jaxlint's static-arg-recompile-hazard sanctions it the same way).

    ``wrapper.__wrapped__`` is the raw factory, as with ``lru_cache``.

    Registering a name here puts the factory under the graph audit's
    contract: ``lint/graph/programs.py`` must carry at least one
    ``ProgramSpec`` covering it (discovery is by AST over this decorator),
    or ``python -m blockchain_simulator_tpu.lint.graph`` fails the
    ``unaudited-factory`` rule in CI.
    """

    def deco(build):
        @functools.wraps(build)
        def wrapper(*args, **kwargs):
            return registry.get(name, args, kwargs, build)

        # lru_cache API parity: per-factory invalidation without touching
        # the other factories sharing the registry (tools/ablate.py relies
        # on make_sim_fn.cache_clear() between patched-op variants)
        wrapper.cache_clear = lambda: registry.clear(name)
        return wrapper

    return deco


# ---------------------------------------------------- jax's compile cache ---


def enable_xla_cache() -> str:
    """Turn on jax's persistent compilation cache for this process and
    return its directory: ``$JAX_COMPILATION_CACHE_DIR`` when the caller's
    environment places it (jax picks that up itself; nothing is set here),
    else the fixed :data:`DEFAULT_XLA_CACHE`.  The entry-size/compile-time
    thresholds are zeroed either way, so a warm process compiles nothing
    and a second identical run adds no entries; HLO metadata (scope names,
    source lines) is part of the key.  Config-level only — never
    touches a backend; call it before the first compile."""
    import jax

    path = os.environ.get(XLA_CACHE_ENV)
    if not path:
        path = DEFAULT_XLA_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the program's jax.named_scope names are HLO metadata, which jax strips
    # from the cache key by default: an executable cached by a build without
    # them (or with other names) would then be loaded in place of this
    # build's, and a profiler trace would show its names, not ours.  With
    # the metadata in the key a cached executable carries the names of the
    # source that asks for it.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    _listen()
    return path


def aot_compile(jitted, example_args: tuple):
    """AOT-stage ``jitted`` for ``example_args``: returns ``(compiled,
    info)`` where ``info`` = ``{"compile_s": float, "cost": {"flops",
    "bytes"} | None}``.  Call it through :func:`aot_cached` (or from a
    :func:`cached_factory`) so repeat invocations skip it entirely."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*example_args).compile()
    return compiled, {"compile_s": time.perf_counter() - t0,
                      "cost": cost_of(compiled)}


def cost_of(staged) -> dict | None:
    """XLA's own {flops, bytes accessed} normalized to ``{"flops",
    "bytes"}``, or None.  ``staged`` is anything exposing
    ``cost_analysis()`` — a compiled executable (the roofline fields
    bench.py puts on its artifact) or a ``jax.stages.Lowered`` (the
    analytical model the graph auditor's budget gate pins,
    lint/graph/ir.py) — so every cost surface in the repo reads the same
    record."""
    try:
        ca = staged.cost_analysis()
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
        }
    except Exception:
        return None


def aot_cached(name: str, jitted_factory, example_args: tuple, cfg=None, extra=None):
    """Registry-memoized :func:`aot_compile`: one entry per (name, cfg,
    extra, input avals).  ``jitted_factory()`` is only called on a miss.
    Returns ``(compiled, info)`` — ``info`` is the build-time record (a
    registry hit returns the original record, ``compile_s`` as paid at
    build time)."""
    import jax

    shapes = tuple(
        (str(getattr(a, "shape", None)), str(getattr(a, "dtype", None)))
        for a in jax.tree.leaves(example_args)
    )
    return registry.get(
        f"aot:{name}",
        (cfg, extra, shapes),
        {},
        lambda *_a, **_k: aot_compile(jitted_factory(), example_args),
    )


# ------------------------------------------------------ build listeners ---

_listening = False
_listen_lock = threading.Lock()


def _listen() -> None:
    """Install the one set of ``jax.monitoring`` listeners that feeds
    ``registry``'s build log; once a process, on the first registry miss or
    :func:`enable_xla_cache`, whichever comes first.  Registering touches no
    backend.  jax calls them a few times per build, and once (a "trace" of
    microseconds, under :data:`FLOOR_S`: no record) per call of a jitted
    function with tracers; never on the dispatch of a compiled program."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring

    log = registry._builds
    monitoring.register_scalar_listener(log.stage_opened)
    monitoring.register_event_time_span_listener(log.stage_closed)
    monitoring.register_event_listener(log.cache_event)
    monitoring.register_event_duration_secs_listener(log.cache_event)
