"""Device-plane runtime observatory (ISSUE 16).

The jit-plane static gates are proof-only: RA13 proves no closure
HAZARD can retrace, RA04 proves the dispatch loop ISSUES no blocking
sync, RA14 proves donation is DECLARED — none of them measure what the
runtime actually did.  A silent retrace (a shape-drifting argument), an
unplanned h2d/d2h transfer, or donation quietly not releasing buffers
shows up only as an unexplained throughput cliff.  This module is the
runtime mirror: three cheap host-side instruments behind one
process-wide singleton (``WATCH``, the ``RECORDER`` idiom), surfaced
as the ``device`` Observatory source / ``DEVICE_FIELDS`` registry
group.

**Recompile sentinel** — :meth:`DeviceWatch.wrap_jit` wraps a jitted
callable in a :class:`_SentinelProxy` that watches the pjit cache size
around each call (``_cache_size()`` is a host-side dict ``len``, no
device work).  Cache growth means THIS call compiled: the proxy
attributes the call's wall time to ``compile_ms``, counts a compile
(and a RECOMPILE when it is not the callable's first), and diffs the
triggering call's abstract signature — shape/dtype/sharding per arg
leaf — against the previously compiled one to name WHICH argument
drifted.  The proxy lives in lockstep's ``_STEP_JIT_CACHE`` next to
the jitted fn it wraps, so engines sharing a cache entry share one
compile count.  Steady-state cost per dispatch: one ``time.monotonic``
+ two cache-size reads + an int compare — the <3% overhead pin in
tests/test_devicewatch.py holds the line.  XLA ``cost_analysis()``
(flops / bytes accessed per compiled variant) is gated behind
``cost_enabled`` because ``lower().compile()`` forces a duplicate
compile — a diagnostic, never an always-on tax.

**Process-wide compile counter** — the sentinel sees only the callables
it wraps; a compile anywhere else (an eager slice of a new shape on a
WAL shard thread, a replay shape at reopen) is invisible to it.
``xla_compiles`` / ``xla_compile_ms`` count EVERY backend compile of
the process and its seconds, whichever thread and whichever program,
through ``jax.monitoring``'s duration listener (a persistent-cache
fetch is a compile request too and counts).  A loop that is warm reads
a delta of zero: "nothing compiles inside the window" as a number.
The listener keeps the ``fun_name`` jax passes with the event: compiles
a program in ``xla_compiles_by_fun`` (``overview()``), and a
``device.compile`` event a compile (ISSUE 37).

**Transfer ledger** — :func:`record_h2d` / :func:`record_d2h` count
transfer events and bytes per named call site (driver staging, window
readbacks, telemetry harvests, mesh sharding, WAL encode readbacks).
The taps are plain host dict increments on metadata the caller already
holds (``.nbytes``), so they are legal inside RA02/RA04-gated closures
— the ledger turns the "fixed per-window transfer budget" from an RA04
lint promise into a measured number.

**Memory watermarks** — :meth:`DeviceWatch.sample_watermarks` reads
live buffer count/bytes from ``jax.live_arrays()`` (host metadata, no
sync) plus per-device allocator stats where the backend exposes them,
called from the TelemetrySampler's existing harvest tick (zero new
syncs — see docs/INTERNALS.md).  ``peak_live_bytes`` is the high-water
mark; ``buffers_freed`` counts net live-buffer releases observed
between samples — under effective donation the live set stays flat
while dispatches grow, so a monotonically growing live set with zero
frees is the donation-regression signature (RA14's runtime twin).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Optional

import jax.monitoring

from .blackbox import record
from .metrics import DEVICE_FIELDS

__all__ = ["DeviceWatch", "WATCH", "record_h2d", "record_d2h",
           "wrap_jit", "sample_watermarks"]


def _leaf_sig(x: Any) -> tuple:
    """(shape, dtype, sharding) of one arg leaf — metadata only."""
    shape = getattr(x, "shape", None)
    if shape is None:
        return ("py", type(x).__name__, "")
    dtype = getattr(x, "dtype", None)
    sharding = getattr(x, "sharding", None)
    return (str(shape), str(dtype), str(sharding) if sharding else "")


def _abstract_sig(args: tuple, kwargs: dict) -> list:
    """[(path, leaf_sig)] for a call's arguments.  Paths come from
    tree_flatten_with_path so the drift report can say ``args[1].log``
    instead of "leaf 17"."""
    import jax

    try:
        leaves, _ = jax.tree_util.tree_flatten_with_path((args, kwargs))
        return [("".join(str(k) for k in path), _leaf_sig(leaf))
                for path, leaf in leaves]
    except Exception:  # noqa: BLE001 — older tree_util: indexed leaves
        leaves = jax.tree_util.tree_leaves((args, kwargs))
        return [(f"leaf[{i}]", _leaf_sig(leaf))
                for i, leaf in enumerate(leaves)]


def _diff_sig(old: Optional[list], new: list) -> str:
    """Name the first drifting argument between two call signatures."""
    if old is None:
        return "first-compile"
    if len(old) != len(new):
        return (f"arg tree structure changed "
                f"({len(old)} -> {len(new)} leaves)")
    for (opath, osig), (npath, nsig) in zip(old, new):
        if osig != nsig:
            what = ("shape" if osig[0] != nsig[0] else
                    "dtype" if osig[1] != nsig[1] else "sharding")
            return (f"{npath or opath}: {what} {osig} -> {nsig}")
    return "signature-identical retrace (cache eviction?)"


def _new_site() -> dict:
    return {"h2d_events": 0, "h2d_bytes": 0,
            "d2h_events": 0, "d2h_bytes": 0}


def _new_fn_entry() -> dict:
    return {"compiles": 0, "recompiles": 0, "compile_ms": 0.0}


class _SentinelProxy:
    """Callable wrapper counting compiles via pjit cache-size growth.

    Attribute access falls through to the wrapped callable, so
    ``.lower()`` / ``._clear_cache()`` callers are unaffected.  The
    proxy is never passed INTO ``jax.jit`` (it wraps the jitted
    output), so it cannot become a traced closure (RA13-safe by
    construction).
    """

    __slots__ = ("_inner", "_tag", "_watch", "_last_sig", "_seen_sigs",
                 "_compiles")

    def __init__(self, inner, tag: str, watch: "DeviceWatch") -> None:
        self._inner = inner
        self._tag = tag
        self._watch = watch
        self._last_sig: Optional[list] = None
        # per-PROXY compile count: a recompile is the 2nd+ compile of
        # THIS wrapped callable — two different-config engines sharing
        # a tag each get one legitimate warm-up compile
        self._compiles = 0
        # fallback for callables without _cache_size (a plain function
        # or an exotic jit wrapper): track signatures we have seen and
        # call a new one a compile
        self._seen_sigs: Optional[set] = None

    def _cache_size(self) -> Optional[int]:
        try:
            return self._inner._cache_size()
        except Exception:  # noqa: BLE001 — no pjit cache introspection
            return None

    def __call__(self, *args, **kwargs):
        w = self._watch
        if not w.enabled:
            return self._inner(*args, **kwargs)
        before = self._cache_size()
        t0 = time.monotonic()
        out = self._inner(*args, **kwargs)
        if before is not None:
            after = self._cache_size()
            if after is not None and after > before:
                self._note_compile(args, kwargs,
                                   (time.monotonic() - t0) * 1e3)
            return out
        # signature-tracking fallback: costs one abstract-sig walk per
        # call, only on backends without cache introspection
        sig = _abstract_sig(args, kwargs)
        if self._seen_sigs is None:
            self._seen_sigs = set()
        key = tuple(s for _p, s in sig)
        if key not in self._seen_sigs:
            self._seen_sigs.add(key)
            self._note_compile(args, kwargs,
                               (time.monotonic() - t0) * 1e3, sig=sig)
        return out

    def _note_compile(self, args, kwargs, ms: float, sig=None) -> None:
        w = self._watch
        if sig is None:
            sig = _abstract_sig(args, kwargs)
        c = w.counters
        ent = w.per_fn[self._tag]
        self._compiles += 1
        c["compiles"] += 1
        c["compile_ms"] += ms
        ent["compiles"] += 1
        ent["compile_ms"] += ms
        if self._compiles > 1:
            c["recompiles"] += 1
            ent["recompiles"] += 1
            drift = _diff_sig(self._last_sig, sig)
            ent["last_drift"] = drift
            record("device.recompile", fn=self._tag, drift=drift,
                   compile_ms=round(ms, 3))
        self._last_sig = sig
        if w.cost_enabled:
            ent["cost"] = self._cost_analysis(args, kwargs)

    def _cost_analysis(self, args, kwargs) -> dict:
        """flops / bytes-accessed of the just-compiled variant.  Forces
        a DUPLICATE compile via lower().compile() — diagnostic only."""
        try:
            ca = self._inner.lower(*args, **kwargs) \
                .compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            return {"flops": float(ca.get("flops", -1.0)),
                    "bytes_accessed": float(
                        ca.get("bytes accessed",
                               ca.get("bytes_accessed", -1.0)))}
        except Exception:  # noqa: BLE001 — donated inputs / no backend
            return {"flops": -1.0, "bytes_accessed": -1.0}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __repr__(self) -> str:
        return f"<sentinel:{self._tag} {self._inner!r}>"


class DeviceWatch:
    """Process-wide device-plane observatory: recompile sentinel +
    transfer ledger + memory watermarks, one ``overview()`` dict."""

    def __init__(self) -> None:
        #: master switch: False = every tap is a no-op pass-through
        #: (the A/B knob of the overhead pin, mirroring
        #: ``RECORDER.enabled``)
        self.enabled = True
        #: opt-in XLA cost_analysis per compiled variant — forces a
        #: duplicate compile per variant, so default-off
        self.cost_enabled = False
        self.counters: dict = {}
        #: tag -> per-wrapped-callable sentinel detail (compiles /
        #: recompiles / compile_ms / last_drift / optional cost)
        self.per_fn: collections.defaultdict = \
            collections.defaultdict(_new_fn_entry)
        #: call-site -> transfer ledger slice; factory keeps dict
        #: allocation OUT of the tap functions (RA08-gated closures
        #: reach them from the mesh ingress wave)
        self.sites: collections.defaultdict = \
            collections.defaultdict(_new_site)
        self._prev_live_buffers: Optional[int] = None
        self._last_census_s = float("-inf")
        # compiles arrive on whichever thread compiled
        self._compile_lock = threading.Lock()
        #: program name (jax's ``fun_name``) -> backend compiles of it
        #: (ISSUE 37), and the newest names in compile order
        self.xla_compiles_by_fun: collections.Counter = \
            collections.Counter()
        self._recent_compiles: collections.deque = \
            collections.deque(maxlen=64)
        self.reset()

    # -- lifecycle --------------------------------------------------------

    def reset(self) -> None:
        """Zero every instrument (tests and bench measured windows)."""
        self.counters = {f: 0 for f in DEVICE_FIELDS}
        self.counters["compile_ms"] = 0.0
        self.counters["xla_compile_ms"] = 0.0
        self.per_fn.clear()
        self.sites.clear()
        self.xla_compiles_by_fun.clear()
        self._recent_compiles.clear()
        self._prev_live_buffers = None
        self._last_census_s = float("-inf")

    # -- recompile sentinel -----------------------------------------------

    def wrap_jit(self, jitted, tag: str):
        """Wrap a jitted callable with the recompile sentinel.
        Idempotent: wrapping a proxy returns it unchanged."""
        if isinstance(jitted, _SentinelProxy):
            return jitted
        return _SentinelProxy(jitted, tag, self)

    # -- process-wide compile counter ---------------------------------------

    def note_backend_compile(self, seconds: float,
                             fun_name: str = "?") -> None:
        """One backend compile of the program ``fun_name``, from
        jax.monitoring's listener: counted, and recorded as a
        ``device.compile`` event with the compiling thread's name."""
        if not self.enabled:
            return
        with self._compile_lock:
            self.counters["xla_compiles"] += 1
            self.counters["xla_compile_ms"] += seconds * 1e3
            self.xla_compiles_by_fun[fun_name] += 1
            self._recent_compiles.append(fun_name)
        record("device.compile", fun=fun_name, s=round(seconds, 6),
               thread=threading.current_thread().name)

    def compiled_names(self, n: int) -> list:
        """The programs of the newest ``n`` backend compiles (at most
        the 64 newest), oldest first."""
        if n <= 0:
            return []
        with self._compile_lock:
            return list(self._recent_compiles)[-n:]

    # -- transfer ledger --------------------------------------------------

    def record_h2d(self, site: str, nbytes: int, events: int = 1) -> None:
        if not self.enabled:
            return
        c = self.counters
        c["h2d_events"] += events
        c["h2d_bytes"] += nbytes
        s = self.sites[site]
        s["h2d_events"] += events
        s["h2d_bytes"] += nbytes

    def record_d2h(self, site: str, nbytes: int, events: int = 1) -> None:
        if not self.enabled:
            return
        c = self.counters
        c["d2h_events"] += events
        c["d2h_bytes"] += nbytes
        s = self.sites[site]
        s["d2h_events"] += events
        s["d2h_bytes"] += nbytes

    # -- memory watermarks ------------------------------------------------

    def sample_watermarks(self, min_interval_s: float = 0.0) -> bool:
        """Live-buffer census, called from the TelemetrySampler harvest
        tick.  ``jax.live_arrays()`` + ``.nbytes`` are host metadata —
        no device sync (the whole point of riding the harvest cadence
        instead of adding one) — but the walk is O(live buffers), so
        harvest callers pass ``min_interval_s`` to cap census frequency
        in buffer-heavy processes; a throttled call returns False
        without sampling."""
        if not self.enabled:
            return False
        if min_interval_s > 0.0 and \
                time.monotonic() - self._last_census_s < min_interval_s:
            return False
        try:
            import jax

            arrs = jax.live_arrays()
            n = len(arrs)
            nbytes = sum(self._safe_nbytes(a) for a in arrs)
        except Exception:  # noqa: BLE001 — backend without live_arrays
            return False
        self._last_census_s = time.monotonic()
        c = self.counters
        c["live_buffers"] = n
        c["live_bytes"] = nbytes
        if nbytes > c["peak_live_bytes"]:
            c["peak_live_bytes"] = nbytes
        prev = self._prev_live_buffers
        if prev is not None and n < prev:
            c["buffers_freed"] += prev - n
        self._prev_live_buffers = n
        c["watermark_samples"] += 1
        return True

    @staticmethod
    def _safe_nbytes(a) -> int:
        try:
            return int(a.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated buffer
            return 0

    def device_memory_stats(self) -> dict:
        """Per-device allocator stats where the backend exposes them
        (TPU/GPU ``memory_stats()``; None on CPU) — diagnostic surface
        for ra_top's ``--once`` deep dive, not part of the sampled
        counter set."""
        out: dict = {}
        try:
            import jax

            for d in jax.local_devices():
                stats = None
                try:
                    stats = d.memory_stats()
                except Exception:  # noqa: BLE001 — CPU backend
                    stats = None
                if stats:
                    out[str(d.id)] = {
                        "bytes_in_use": int(stats.get("bytes_in_use", -1)),
                        "peak_bytes_in_use": int(
                            stats.get("peak_bytes_in_use", -1)),
                    }
        except Exception:  # noqa: BLE001 — no jax at all
            pass
        return out

    # -- surface ----------------------------------------------------------

    def overview(self) -> dict:
        """The ``device`` Observatory source: flat DEVICE_FIELDS
        counters plus nested per-callable sentinel detail and the
        per-site transfer ledger (the Observatory flattens nesting
        into ``device_per_fn_<tag>_<field>`` ring keys)."""
        snap = dict(self.counters)
        snap["per_fn"] = {
            tag: {k: v for k, v in ent.items() if k != "cost"}
            for tag, ent in self.per_fn.items()}
        for tag, ent in self.per_fn.items():
            cost = ent.get("cost")
            if cost:
                snap["per_fn"][tag].update(
                    {f"cost_{k}": v for k, v in cost.items()})
        snap["sites"] = {site: dict(s) for site, s in self.sites.items()}
        with self._compile_lock:
            snap["xla_compiles_by_fun"] = dict(self.xla_compiles_by_fun)
        return snap


#: the process-wide device watch (the RECORDER idiom): importers call
#: the module-level taps so instrumentation sites stay one line
WATCH = DeviceWatch()


#: jax's duration event around every backend compile (a persistent-
#: cache fetch included), jax/_src/dispatch.py BACKEND_COMPILE_EVENT
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event_duration(event: str, duration_secs: float,
                       fun_name: str = "?", **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        WATCH.note_backend_compile(duration_secs, str(fun_name))


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def wrap_jit(jitted, tag: str):
    return WATCH.wrap_jit(jitted, tag)


def record_h2d(site: str, nbytes: int, events: int = 1) -> None:
    WATCH.record_h2d(site, nbytes, events)


def record_d2h(site: str, nbytes: int, events: int = 1) -> None:
    WATCH.record_d2h(site, nbytes, events)


def sample_watermarks(min_interval_s: float = 0.0) -> bool:
    return WATCH.sample_watermarks(min_interval_s)


def bench_tail_keys(commands: Optional[int] = None) -> dict:
    """The device-plane soak JSON-tail stamp (ISSUE 16): ONE
    definition of the keys a reader compares run over run —
    ``n_compiles`` (must not grow round-over-round), ``compile_time_s``,
    ``transfer_bytes`` (+ ``transfer_bytes_per_cmd`` when the caller
    passes its command count), ``peak_live_bytes``.  Values are
    process-lifetime totals: warm-up compiles are part of a round's
    compile budget, and a round-over-round n_compiles GROWTH is
    exactly the retrace regression the diff flags."""
    c = WATCH.counters
    out = {
        "n_compiles": c["compiles"],
        "n_recompiles": c["recompiles"],
        "compile_time_s": round(c["compile_ms"] / 1e3, 6),
        "transfer_bytes": c["h2d_bytes"] + c["d2h_bytes"],
        "peak_live_bytes": c["peak_live_bytes"],
    }
    if commands:
        out["transfer_bytes_per_cmd"] = round(
            out["transfer_bytes"] / commands, 4)
    return out
