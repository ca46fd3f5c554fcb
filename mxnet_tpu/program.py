"""One compiled-program artifact, shared by every execution path and
persisted across processes.

The serving cache (``serving/compiled.py``), the fused trainer step
(``parallel/trainer.py``), and the legacy ``executor.py`` bind path all
used to lower and compile privately — three copies of the same
symbol → jaxpr → lowered → executable pipeline, none of which survived
a process exit, so elastic recovery, serving ``start()`` warmup, and
every CI rerun paid full trace+compile again.  :class:`CompiledProgram`
is the one artifact all three consume (the whole-program-compilation
model of the Julia-to-TPU work, PAPERS.md):

* **counted** — the traced python body runs exactly once per distinct
  input signature, so ``trace_count`` is the compilation counter;
  signatures registered through :meth:`aot` are deliberate, everything
  else is a lazy trace (a retrace on somebody's hot path).  One
  accounting scheme for trainer, executor, and serving.
* **keyed** — identity is the ``key`` dict (symbol digest, dtype
  policy, platform, mesh/partition plan, optimizer config, …) plus the
  per-call abstract signature (shapes, dtypes, shardings).  Anything
  that changes the compiled bytes must appear in one of the two.
* **persisted** — with ``MXTPU_PROGRAM_CACHE=<dir>`` armed, every
  compile serializes its AOT executable to disk
  (``jax.experimental.serialize_executable`` + the ``resilience.py``
  manifest-commit recipe: tmp write, fsync, atomic rename) and every
  first-use-of-a-signature probes the cache first.  A second process
  over the same (symbol, shapes, policy, mesh) **compiles zero
  programs**: restarts, serving cold starts, and CI reruns load
  executables instead of tracing.  A stale, truncated, or
  wrong-version entry is a MISS (recompile), never a crash.

Accounting surfaces through :func:`cache_stats` and the obs registry
(``program.cache_hit`` / ``program.cache_miss`` / ``program.cache_stale``
counters; ``compile.trace`` / ``compile.compile`` / ``compile.load``
spans) — ``tools/obs_report.py`` shows where startup time went.
See docs/how_to/compiled_programs.md.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import weakref
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from .base import MXNetError
from . import _tsan
from . import obs as _obs

__all__ = ["CompiledProgram", "jit", "cache_dir", "place_compile_cache",
           "cache_stats", "reset_stats", "stats_delta", "entry_path",
           "symbol_digest", "PROGRAM_CACHE_VERSION"]

# bump when the on-disk entry layout changes: older entries become
# stale misses, never parse errors
PROGRAM_CACHE_VERSION = 2

# hit/miss/stale accounting in the process-wide metrics registry —
# always on (the registry is), scraped via obs.snapshot() and reported
# by tools/obs_report.py
_HITS = _obs.counter("program.cache_hit")
_MISSES = _obs.counter("program.cache_miss")
_STALE = _obs.counter("program.cache_stale")
_COMPILES = _obs.counter("program.compiles")
_LOADS = _obs.counter("program.loads")
_PERSISTS = _obs.counter("program.persists")

_STATS_LOCK = _tsan.lock("program._STATS_LOCK")
# weak registry so cache_stats() can sum live programs' counters
# without pinning dead trainers/servers in memory
_PROGRAMS: "weakref.WeakSet[CompiledProgram]" = weakref.WeakSet()


def cache_dir() -> Optional[str]:
    """The persisted-program cache directory (``MXTPU_PROGRAM_CACHE``),
    or None when persistence is off.  Read per call: tests and the
    warm-restart drill flip it at runtime."""
    d = os.environ.get("MXTPU_PROGRAM_CACHE") or None
    return d


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place_compile_cache(programs: bool = False) -> str:
    """Keep compiled code between runs; every entry script calls this
    before its first compile.  Returns the root of JAX's cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    persistent cache there and nothing is set here.  Where it is not,
    the cache is ``<checkout>/.jax_cache``: a fixed path, because the
    path is part of what a cache entry is found by.

    ``programs=True`` also arms ``MXTPU_PROGRAM_CACHE`` (unless the
    caller's environment already did) at ``<checkout>/.jax_cache/
    programs``.  It stays under the checkout even where JAX's cache is
    placed from outside: the two caches hold every executable twice,
    and the chip tool drops a placed cache that passes 256 MiB
    (``chip_smoke.py`` left 353 MB with both under one root)."""
    local = os.path.join(_CHECKOUT, ".jax_cache")
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not root:
        root = local
        jax.config.update("jax_compilation_cache_dir", root)
    if programs:
        os.environ.setdefault("MXTPU_PROGRAM_CACHE",
                              os.path.join(local, "programs"))
    return root


def _jax_version() -> str:
    """Part of every cache key: an executable serialized by one
    jax/jaxlib must never execute under another (monkeypatched by the
    invalidation tests)."""
    import jaxlib
    return "%s/%s" % (jax.__version__,
                      getattr(jaxlib, "__version__", "?"))


def _backend() -> str:
    """Part of every cache key (monkeypatched by the invalidation
    tests).  A backend that cannot be asked is an error, not a key."""
    return jax.default_backend()


def symbol_digest(symbol) -> str:
    """The cache-identity digest of a Symbol (sha1 of its JSON) — THE
    one definition; trainer, executor, and serving all key their
    programs through it, so a canonicalization change can never fork
    the keyspace between layers."""
    return hashlib.sha1(symbol.tojson().encode()).hexdigest()


def _leaf_sig(v) -> Tuple:
    """(shape, dtype, sharding) of one abstract or concrete leaf.

    Sharding is normalized: an uncommitted array and an array committed
    to the DEFAULT device produce the same component (XLA compiles the
    same executable for both, and jit's own cache treats them alike) —
    otherwise the first step's uncommitted inputs and every later
    step's committed outputs would key two entries for one program.
    Mesh/NamedShardings keep their full string form (axis names, mesh
    shape, spec): a resharded input IS a different program."""
    shape = tuple(getattr(v, "shape", ()))
    try:
        dtype = str(np.dtype(v.dtype))
    except Exception:               # noqa: BLE001 — extended dtypes
        dtype = str(getattr(v, "dtype", type(v)))   # (PRNG keys)
    sh = getattr(v, "sharding", None)
    if isinstance(v, jax.Array) and not getattr(v, "_committed", False):
        sh = None
    if sh is not None:
        try:
            from jax.sharding import SingleDeviceSharding
            if isinstance(sh, SingleDeviceSharding) and \
                    list(sh.device_set)[0] == jax.devices()[0]:
                sh = None
        except Exception:           # noqa: BLE001
            pass
    return (shape, dtype, str(sh) if sh is not None else "")


def _args_sig(args) -> str:
    """Stable digest of an argument pytree's abstract signature:
    structure + per-leaf (shape, dtype, normalized sharding)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    h = hashlib.sha1(str(treedef).encode())
    for v in leaves:
        h.update(repr(_leaf_sig(v)).encode())
    return h.hexdigest()


def _contains_tracer(args) -> bool:
    return any(isinstance(v, jax.core.Tracer)
               for v in jax.tree_util.tree_leaves(args))


class CompiledProgram:
    """A python step/forward function as one compiled, countable,
    persistable artifact.

    Parameters
    ----------
    kind : str
        artifact family (``trainer.step``, ``serving.forward``,
        ``executor.forward``, …) — part of the cache key and the obs
        span attribution.
    fn : callable
        the pure function to jit.  The traced body is wrapped with the
        trace counter; jax runs it once per distinct signature.
    key : dict, optional
        identity fields beyond the abstract call signature (symbol
        digest, dtype policy, optimizer config, mesh plan, …).  None
        disables DISK persistence — the program still counts traces
        and registers AOT signatures in memory.
    jit_kwargs : dict, optional
        forwarded to ``jax.jit`` (in/out_shardings, donate_argnums).
    meta : dict, optional
        attached artifact metadata that rides the object (sharding
        plan, donation map, named scopes, lint findings) — not part of
        the key; surfaced via :attr:`meta` for tools.
    """

    def __init__(self, kind: str, fn: Callable, *,
                 key: Optional[Dict[str, Any]] = None,
                 jit_kwargs: Optional[Dict[str, Any]] = None,
                 meta: Optional[Dict[str, Any]] = None):
        self.kind = kind
        self.fn = fn
        self.key = dict(key) if key is not None else None
        self.meta = dict(meta or {})
        self.trace_count = 0
        self._lazy_sigs: List[str] = []   # one entry per lazy trace
        self._aot_keys: set = set()
        self._loaded: Dict[str, Any] = {}      # sig -> Compiled (disk)
        self._probed: set = set()              # sigs disk-probed
        # set once a lazy-call probe MISSED with nothing loaded: from
        # then on __call__ is the plain-jit fast path — per-call
        # signature hashing is paid only while it can buy a dispatch
        # decision (a loaded executable, or an unprobed first sig),
        # never as a fixed per-step tax (the dispatch-overhead class
        # the integrity work measured at ~0.2 ms and removed)
        self._jit_only = False
        self._aot_tls = threading.local()
        self._lock = _tsan.lock("program.CompiledProgram._lock")
        self.disk_loads = 0
        self.disk_misses = 0
        self.dispatch_fallbacks = 0
        # single-signature dispatch memo: once ONE loaded executable
        # has dispatched successfully and it is the only one, later
        # calls try it directly — Compiled.__call__ validates avals
        # itself (TypeError on mismatch drops the memo), so the
        # per-call signature hashing is never a fixed per-step tax on
        # the warm path either
        self._fast_comp = None

        def _counted(*args):
            # trace-time side effect: jax runs this exactly once per
            # distinct signature — the compilation counter.  The AOT
            # flag is thread-local (aot()'s lower() traces on the
            # calling thread), so a concurrent lazy trace elsewhere is
            # still attributed correctly.
            with self._lock:
                if _tsan.TSAN:
                    _tsan.note_write("program.CompiledProgram.counters")
                self.trace_count += 1
                lazy = not getattr(self._aot_tls, "active", False)
                if lazy:
                    self._lazy_sigs.append(self._trace_tag(args))
                self._on_trace(args, lazy)
            return fn(*args)

        self._jit = jax.jit(_counted, **(jit_kwargs or {}))
        with _STATS_LOCK:
            _PROGRAMS.add(self)

    # -- subclass hooks ------------------------------------------------
    def _on_trace(self, args, lazy: bool) -> None:
        """Called (under the counter lock) on every trace — subclasses
        record extra provenance (CompiledForward: the batch size)."""

    def _trace_tag(self, args) -> str:
        """Label recorded per LAZY trace (default: the kind)."""
        return self.kind

    def _call_sig(self, args) -> str:
        """The dispatch/persistence signature of one concrete call."""
        return _args_sig(args)

    # -- jit passthroughs (stepcost.py, lint, make_jaxpr) --------------
    @property
    def jit(self):
        """The underlying ``jax.jit`` object (trace-level consumers:
        ``jax.make_jaxpr``, ``.lower()`` cost analysis)."""
        return self._jit

    def lower(self, *args, **kw):
        return self._jit.lower(*args, **kw)

    # -- disk cache ----------------------------------------------------
    def _entry_ident(self, sig: str) -> Dict[str, Any]:
        return {"kind": self.kind, "key": self.key, "sig": sig,
                "jax": _jax_version(), "backend": _backend(),
                "nproc": jax.process_count(),
                "v": PROGRAM_CACHE_VERSION}

    def _entry_key(self, sig: str) -> Optional[str]:
        # hashed from the SAME dict _try_load verifies against — a
        # field added to the ident can never desync the filename from
        # the embedded identity (which would turn every load into a
        # silent stale miss)
        if self.key is None:
            return None
        blob = json.dumps(self._entry_ident(sig), sort_keys=True,
                          default=str)
        return hashlib.sha256(blob.encode()).hexdigest()

    def _try_load(self, sig: str, directory: str):
        """One disk probe for ``sig``.  Returns the loaded executable
        or None.  EVERY failure mode — missing file, truncated bytes,
        CRC mismatch, foreign jax version, deserialization error — is
        a counted miss/stale, never an exception on the caller."""
        ekey = self._entry_key(sig)
        path = os.path.join(directory, ekey + ".mxprog")
        if not os.path.exists(path):
            _MISSES.inc()
            with self._lock:
                self.disk_misses += 1
            return None
        try:
            with open(path, "rb") as f:
                entry = pickle.loads(f.read())
            meta = entry["meta"]
            payload = entry["payload"]
            if meta.get("ident") != json.loads(
                    json.dumps(self._entry_ident(sig), default=str)):
                raise ValueError("key fields do not match")
            if zlib.crc32(payload) & 0xFFFFFFFF != meta["crc32"] \
                    or len(payload) != meta["size"]:
                raise ValueError("payload CRC/size mismatch")
            from jax.experimental import serialize_executable as _se
            # onto the devices it was compiled for: left to itself the
            # loader binds the executable to every device of the default
            # backend.  On a host with more devices than the program
            # spans (four chips, the eight-device test mesh) the call
            # then fails, and a CPU program of a TPU process fails to
            # parse
            platform, ids = entry["devices"]
            by_id = {d.id: d for d in jax.devices(platform)}
            devices = [by_id[i] for i in ids]
            with _obs.phase("compile.load",
                            attrs={"kind": self.kind,
                                   "bytes": len(payload)}):
                comp = _se.deserialize_and_load(
                    payload, entry["in_tree"], entry["out_tree"],
                    backend=platform, execution_devices=devices)
        except Exception as e:      # noqa: BLE001 — stale = miss
            _STALE.inc()
            with self._lock:
                self.disk_misses += 1
            import logging
            logging.getLogger("mxtpu.program").warning(
                "program cache entry %s is stale/corrupt (%s: %s) — "
                "recompiling", os.path.basename(path),
                type(e).__name__, e)
            return None
        _HITS.inc()
        _LOADS.inc()
        with self._lock:
            if _tsan.TSAN:
                _tsan.note_write("program.CompiledProgram.counters")
            self.disk_loads += 1
            self._loaded[sig] = comp
            self._aot_keys.add(sig)   # a loaded sig is pre-compiled
        return comp

    def _persist(self, sig: str, compiled, directory: str) -> None:
        """Serialize + atomically commit one executable.  Best-effort:
        an unserializable program (exotic backend) or a read-only dir
        degrades to in-memory behavior with a logged warning."""
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            meta = {"ident": json.loads(json.dumps(
                self._entry_ident(sig), default=str)),
                "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
                "size": len(payload)}
            devs = compiled._executable._unloaded_executable.device_list
            devices = (devs[0].platform, [d.id for d in devs])
            blob = pickle.dumps({"meta": meta, "payload": payload,
                                 "in_tree": in_tree,
                                 "out_tree": out_tree,
                                 "devices": devices})
            os.makedirs(directory, exist_ok=True)
            path = os.path.join(directory,
                                self._entry_key(sig) + ".mxprog")
            # manifest-commit recipe (resilience.py) with a PER-PROCESS
            # tmp name: two ranks of a shared-cache launch persist the
            # same entry key concurrently (same symbol/mesh/nproc), and
            # a fixed '<path>.tmp' would interleave their bytes —
            # whichever rename lands last must still commit a whole
            # file
            tmp = "%s.%d.tmp" % (path, os.getpid())
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            _PERSISTS.inc()
        except Exception as e:      # noqa: BLE001 — persistence is an
            import logging          # optimization, never a failure
            logging.getLogger("mxtpu.program").warning(
                "could not persist %s program (%s: %s) — running "
                "in-memory only", self.kind, type(e).__name__, e)

    # -- compilation ---------------------------------------------------
    def _lower_compile(self, args) -> Any:
        """``.lower().compile()`` with spans + counters; the resulting
        executable also lands in jax's own jit cache, so a later
        ``self._jit(*args)`` at this signature is a pure cache hit."""
        with _obs.phase("compile.trace", attrs={"kind": self.kind}):
            lowered = self._jit.lower(*args)
        with _obs.phase("compile.compile", attrs={"kind": self.kind}):
            compiled = lowered.compile()
        _COMPILES.inc()
        return compiled

    def aot(self, *args) -> str:
        """Compile one input signature ahead of time (``args`` may be
        values or ShapeDtypeStructs).  Returns ``"cached"`` (already
        known), ``"loaded"`` (deserialized from the program cache — no
        trace, no compile), or ``"compiled"`` (traced + compiled now,
        and persisted when the cache is armed)."""
        sig = self._call_sig(args)
        with self._lock:
            if sig in self._aot_keys:
                return "cached"
        d = cache_dir()
        if d is not None and self.key is not None:
            with self._lock:
                probe = sig not in self._probed
                self._probed.add(sig)
            if probe and self._try_load(sig, d) is not None:
                return "loaded"
            with self._lock:
                if sig in self._loaded:
                    return "loaded"
        self._aot_tls.active = True
        try:
            compiled = self._lower_compile(args)
        finally:
            self._aot_tls.active = False
        with self._lock:
            if _tsan.TSAN:
                _tsan.note_write("program.CompiledProgram.counters")
            self._aot_keys.add(sig)
            if not self._loaded:
                # cold cache for this program: calls dispatch through
                # the jit's own cache, so run()s skip the per-call
                # signature hashing (a later aot() that LOADS clears
                # the latch's effect — the fast path requires _loaded
                # to be empty)
                self._jit_only = True
        if d is not None and self.key is not None:
            self._persist(sig, compiled, d)
        return "compiled"

    def loaded_from_disk(self, *args) -> bool:
        """True when this signature's executable came off the program
        cache (the server's start() skips the execute-once dispatch
        warmup for those — docs/how_to/serving.md)."""
        sig = self._call_sig(args)
        with self._lock:
            return sig in self._loaded

    def __call__(self, *args):
        # fast path: nothing loaded from disk and persistence off (or
        # already resolved to the jit) — exactly the plain-jit behavior
        # (and cost) this class replaced
        d = cache_dir()
        if not self._loaded and (self._jit_only or d is None
                                 or self.key is None):
            return self._jit(*args)
        fc = self._fast_comp
        if fc is not None:
            try:
                return fc(*args)
            except TypeError:   # aval drift: back to the full path
                self._fast_comp = None
        if _contains_tracer(args):
            # somebody is tracing THROUGH the program (make_jaxpr,
            # vjp): inline the jit like a plain call would
            return self._jit(*args)
        sig = self._call_sig(args)
        with self._lock:
            comp = self._loaded.get(sig)
        if comp is None and d is not None and self.key is not None:
            with self._lock:
                probe = sig not in self._probed
                self._probed.add(sig)
            if probe:
                comp = self._try_load(sig, d)
                if comp is None:
                    # miss: compile now (counted as a lazy trace — the
                    # caller's first step) and persist for the next
                    # process
                    compiled = self._lower_compile(args)
                    self._persist(sig, compiled, d)
                    with self._lock:
                        # cold cache, nothing loaded: later calls are
                        # pure jit dispatch (a LATER new signature on
                        # this same object won't disk-probe — lazy
                        # multi-sig programs are the serving fallback
                        # path, a deliberate retrace either way)
                        if not self._loaded:
                            self._jit_only = True
        if comp is not None:
            try:
                out = comp(*args)
                with self._lock:
                    if len(self._loaded) == 1:
                        self._fast_comp = comp
                return out
            except TypeError:
                # aval/sharding drift vs the loaded executable: fall
                # back to jit (trace), count it — never wrong-program
                with self._lock:
                    if _tsan.TSAN:
                        _tsan.note_write(
                            "program.CompiledProgram.counters")
                    self.dispatch_fallbacks += 1
                    self._loaded.pop(sig, None)
        return self._jit(*args)

    # -- accounting ----------------------------------------------------
    def counts(self) -> Dict[str, Any]:
        """One atomic snapshot of the trace/compile/load accounting."""
        with self._lock:
            if _tsan.TSAN:
                _tsan.note_read("program.CompiledProgram.counters")
            d = {"traces": self.trace_count,
                 "aot": len(self._aot_keys),
                 "retraces": len(self._lazy_sigs),
                 "lazy": list(self._lazy_sigs),
                 "disk_loads": self.disk_loads,
                 "disk_misses": self.disk_misses,
                 "dispatch_fallbacks": self.dispatch_fallbacks}
            self._extend_counts(d)
            return d

    def _extend_counts(self, d: Dict[str, Any]) -> None:
        """Subclass hook, called under the counter lock."""


def jit(kind: str, fn: Callable, **jit_kwargs) -> CompiledProgram:
    """A :class:`CompiledProgram` with no disk key — the drop-in for a
    bare ``jax.jit`` on the unified paths (state init, integrity
    fingerprint/vote programs): counted and lint-visible, in-memory
    only."""
    return CompiledProgram(kind, fn, key=None, jit_kwargs=jit_kwargs)


def entry_path(directory: str, ekey: str) -> str:
    return os.path.join(directory, ekey + ".mxprog")


def cache_stats() -> Dict[str, int]:
    """Process-wide program accounting (the warm-restart gates assert
    on this): compiles/persists/loads plus every live program's trace
    counters summed."""
    with _STATS_LOCK:
        programs = list(_PROGRAMS)
    c = [p.counts() for p in programs]
    return {
        "programs": len(programs),
        "traces": sum(x["traces"] for x in c),
        "retraces": sum(x["retraces"] for x in c),
        "compiles": int(_COMPILES.value),
        "loads": int(_LOADS.value),
        "persists": int(_PERSISTS.value),
        "cache_hit": int(_HITS.value),
        "cache_miss": int(_MISSES.value),
        "cache_stale": int(_STALE.value),
    }


def reset_stats() -> None:
    """Zero the module counters (test isolation)."""
    for ctr in (_HITS, _MISSES, _STALE, _COMPILES, _LOADS, _PERSISTS):
        ctr.set(0)


class stats_delta:
    """``with program.stats_delta() as d: <trial>`` — on exit ``d``
    holds the per-counter difference of :func:`cache_stats` across the
    block.  The autotuner's trial-isolation primitive: a timed window
    over a previously-seen config against a warm ``MXTPU_PROGRAM_CACHE``
    must show ``d["compiles"] == 0`` (re-evaluation is compile-free —
    loads and cache hits only), and the tune test asserts exactly that.
    """

    def __enter__(self) -> Dict[str, int]:
        self._before = cache_stats()
        self._d: Dict[str, int] = {}
        return self._d

    def __exit__(self, *exc):
        after = cache_stats()
        self._d.update({k: after[k] - self._before.get(k, 0)
                        for k in after})
        return False
