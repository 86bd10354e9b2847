"""VortexKernel: the end-to-end sample-free compiler (paper Fig. 6).

Offline stage (no shape samples anywhere):
  1. top-down: describe the workload as an rKernel program (workloads.py
     declares it; rkernel.py holds the layer metadata),
  2. bottom-up: generate the hardware-pruned candidate lattice per backend
     (candidates.py, Algorithm 2),
  3. score it with the hybrid analyzer (analyzer.py).

Runtime stage:
  4. given the actual shape, select strategy + launch geometry + backend
     (selector.py) — a bisect into the offline-materialized selection table
     (selection_table.py) on the hot path, the fused analytical argmin past
     the table,
  5. construct/fetch the executable for the induced bucket and run (skipping
     pad/unpad entirely when the extent is already bucket-aligned).

:class:`VortexKernel` drives ANY registered
:class:`~repro.core.workloads.Workload` through the same lattice → analyzer →
selector → bucketed-executable pipeline.  The multi-workload session layer —
one engine serving every registered kind from one scored-lattice cache and
one dispatch table — lives in :mod:`repro.vortex` (the public API);
``VortexEngine``/``VortexGemm`` remain importable from here as deprecation
shims over that package.

Execution backends:
  * ``xla``    — flat JAX ops on the bucket shape (host-CPU execution in
                 this container; what the benchmarks time),
  * ``pallas`` — the Vortex-tiled Pallas TPU kernels (kernels/) with
                 BlockSpecs taken from the selected strategy and the
                 hardware's level-1 capacity as their VMEM limit; they
                 compile natively on a TPU and run in interpret mode only
                 on the CPU (``kernels.gemm.interpret_pallas``).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, as_completed
from typing import Any, Callable

import jax

from repro.core.analyzer import HybridAnalyzer, Profiler, ScoredLattice
from repro.core.candidates import generate_lattice
from repro.core.hardware import HardwareSpec
from repro.core.selector import RuntimeSelector, Selection
from repro.core.workloads import Workload
from repro.runtime import faults

__all__ = [
    "DispatchStats",
    "OfflineStats",
    "PrecompileError",
    "VortexKernel",
    "VortexGemm",
    "VortexEngine",
]


@dataclasses.dataclass(frozen=True)
class OfflineStats:
    """Offline-stage accounting (paper §7.4 'Offline Overhead Analysis')."""

    num_candidates: int
    num_measured: int
    build_seconds: float
    backends: tuple[str, ...]


class PrecompileError(RuntimeError):
    """A bucket failed to compile during :meth:`VortexKernel.precompile`.

    Parallel precompiles surface through ``as_completed`` futures, which
    would otherwise raise the bare builder exception with no hint of WHICH
    bucket died; this wrapper names the failing Selection so a fleet-wide
    warmup failure is diagnosable from the message alone.
    """

    def __init__(self, kind: str, sel: Selection, cause: BaseException):
        self.kind = kind
        self.selection = sel
        super().__init__(
            f"precompile failed for workload {kind!r}: bucket={sel.bucket} "
            f"backend={sel.backend} strategy l1={sel.strategy.l1} "
            f"grid={sel.grid}: {type(cause).__name__}: {cause}"
        )


@dataclasses.dataclass
class DispatchStats:
    """Per-call accounting for the serving hot path (the numbers the
    Fig. 8/Fig. 14 'padding confined to the outermost level' claim is
    checked against).

    ``launches`` counts executions of the ONE fused per-bucket program;
    ``stage_copies``/``unstage_copies`` count the O(true-size) boundary
    copies an unaligned extent pays (dynamic_update_slice into a donated
    engine buffer / the output slice back).  ``padded_calls`` counts falls
    back to the zero-pad reference path (tracer-context calls and
    workloads without staging support); ``traced_calls`` counts calls that
    arrived as tracers inside an enclosing jit (they become part of the
    surrounding program, not runtime launches).

    ``quarantined`` counts candidates the degradation ladder denylisted
    after a precompile/launch failure; ``fallbacks`` counts dispatches
    that exhausted the lattice retries and ran the XLA reference rung.
    Both are zero on every healthy host (DESIGN.md §11).
    """

    calls: int = 0
    launches: int = 0
    aligned_calls: int = 0
    unaligned_calls: int = 0
    stage_copies: int = 0
    unstage_copies: int = 0
    padded_calls: int = 0
    traced_calls: int = 0
    fallbacks: int = 0
    quarantined: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@functools.partial(jax.jit, donate_argnums=0)
def _stage_into(buf, x):
    """Copy ``x`` into the leading corner of the engine-owned bucket buffer
    IN PLACE (``buf`` is donated): only the true extent is written, the pad
    tail keeps whatever stale bytes it held — the masked-tail kernels never
    read them — and no fresh zero-filled allocation is made."""
    return jax.lax.dynamic_update_slice(buf, x, (0,) * buf.ndim)


class _StagingPool:
    """A small pool of engine-owned staging-buffer SETS for one cache entry.

    One set (a dict mapping view-arg index -> bucket-shaped buffer) serves
    one in-flight unaligned dispatch: concurrent same-bucket calls each
    check out their own set, stage and launch WITHOUT any entry-wide lock,
    and return the set afterwards — the per-dtype-singleton design this
    replaces serialized staging AND the launch of every concurrent
    same-bucket call behind one lock (ROADMAP: multi-tenant serialization).

    The pool lock covers only the list pop/append (nanoseconds).  A set's
    buffers keep whatever stale bytes the last staging left past the true
    extent — never re-zeroed; correctness is the kernel's kv_len/m_true
    masking (the poisoned-staging tests assert it).  Retention is an LRU
    bounded at ``cap`` sets (``EngineConfig.staging_pool_cap``): a release
    lands at the MRU end and evicts from the LRU end when over cap, so a
    burst beyond the cap allocates transient sets that age out instead of
    pinning device memory forever.  Eviction can never touch an in-flight
    dispatch: a checked-out set is not in the free list at all until its
    caller releases it.
    """

    __slots__ = ("cap", "_lock", "_free")

    def __init__(self, cap: int = 4):
        self.cap = cap
        self._lock = threading.Lock()
        self._free: list[dict] = []

    def acquire(self, need: dict) -> dict:
        """A buffer set satisfying ``need`` (index -> (shape, dtype)).
        Reuses a pooled set when every needed slot matches; otherwise
        builds fresh zero-initialized buffers (zeros only because a fresh
        buffer must not leak other tenants' bytes through the never-read
        pad — the kernels never rely on it)."""
        with self._lock:
            # MRU-first scan: the most recently released set is the most
            # likely to still match (and the least likely to be evicted).
            for i in range(len(self._free) - 1, -1, -1):
                bufs = self._free[i]
                for idx, (shape, dtype) in need.items():
                    b = bufs.get(idx)
                    if b is None or b.shape != shape or b.dtype != dtype:
                        break
                else:
                    return self._free.pop(i)
        return {
            idx: jax.numpy.zeros(shape, dtype)
            for idx, (shape, dtype) in need.items()
        }

    def release(self, bufs: dict) -> None:
        with self._lock:
            self._free.append(bufs)  # MRU end
            while len(self._free) > self.cap:
                self._free.pop(0)  # evict LRU

    @property
    def retained(self) -> list[dict]:
        """The currently pooled buffer sets (tests poison these)."""
        return self._free


@dataclasses.dataclass
class _CacheEntry:
    """One fused per-bucket program + its engine-owned staging state.

    ``fn`` is the dtype-flexible jitted program (also what tracer-context
    calls inline); ``aot`` is the AOT ``lower().compile()`` artifact for the
    bucket's canonical dtypes — the steady-state serve path, which skips
    jit's dispatch machinery entirely.  ``pool`` holds the engine-owned
    bucket-shaped staging buffer sets (created lazily on the first
    unaligned call; their pad regions are NEVER re-zeroed — correctness
    is the kernel's masking, asserted by the poisoned-staging tests).
    """

    fn: Callable
    compile_seconds: float
    aot: Any = None
    aot_dtypes: tuple = ()
    hits: int = 0
    pool: _StagingPool = dataclasses.field(default_factory=_StagingPool)

    def run(self, *args):
        if faults.ACTIVE is not None:
            faults.ACTIVE.check("aot_launch")
        if self.aot is not None and len(args) == len(self.aot_dtypes):
            for a, d in zip(args, self.aot_dtypes):
                if getattr(a, "dtype", None) != d:
                    break
            else:
                return self.aot(*args)
        return self.fn(*args)


class VortexKernel:
    """One dynamic-shape workload, compiled sample-free.

    Generic over the Workload protocol: the workload declares its lattice
    footprints, its runtime-dims view and its executable builder; this class
    owns the offline build (lattice + scoring, optionally shared through
    ``scored_cache``), the runtime selector and the bucketed executable
    cache.  This is the unit the paper evaluates (BERT GEMMs with
    M = batch*seq; attention/conv ride the same machinery).

    ``table_m_max``/``table_extend_limit`` size the selector's offline
    selection table (see selector.py); they are what
    :class:`repro.vortex.EngineConfig` threads through.
    """

    def __init__(
        self,
        hw: HardwareSpec,
        wl: Workload,
        profiler: Profiler | None = None,
        empirical_levels: tuple[int, ...] = (0,),
        backends: tuple[str, ...] | None = None,
        num_cores: int = 1,
        impl: str = "xla",
        scored_cache: dict | None = None,
        table_m_max: int = 4096,
        table_extend_limit: int = 1 << 17,
        staging: bool = True,
        staging_pool_cap: int = 4,
        max_retries: int = 2,
        denylist=None,
    ):
        self._hw = hw
        self._wl = wl
        self._impl = impl
        self._staging = staging and wl.supports_staging
        self._pool_cap = staging_pool_cap
        self._max_retries = max(int(max_retries), 0)
        # The degradation ladder's quarantine (DESIGN.md §11): string keys
        # of candidates that failed at precompile or launch on THIS host.
        # Seeded from the persisted denylist (same fingerprint key as the
        # calibration cache) so restarts never re-fail a known-bad
        # candidate; empty on every healthy host, so the hot path pays one
        # falsy set check.
        self._denylist = denylist
        self._sig_key = repr(wl.signature)
        self._quarantined: set[str] = (
            set(denylist.get(self._sig_key)) if denylist is not None
            else set()
        )
        self.dispatch_stats = DispatchStats()
        t0 = time.perf_counter()
        backends = backends or tuple(hw.backends)
        scored: dict[str, ScoredLattice] = {}
        n_cands = 0
        n_meas = 0
        for backend in backends:
            cache_key = (wl.lattice_key, hw.name, backend, empirical_levels)
            hit = scored_cache.get(cache_key) if scored_cache is not None \
                else None
            if hit is not None:
                scored[backend] = hit
                continue
            lattice = generate_lattice(hw, wl, backend)
            n_cands += lattice.num_candidates()
            analyzer = HybridAnalyzer(
                hw, wl, profiler=profiler, empirical_levels=empirical_levels
            )
            sl = analyzer.score(lattice)
            n_meas += sl.num_measured
            scored[backend] = sl
            if scored_cache is not None:
                scored_cache[cache_key] = sl
        self.selector = RuntimeSelector(
            hw, wl, scored, num_cores=num_cores,
            table_m_max=table_m_max, table_extend_limit=table_extend_limit,
        )
        self.offline_stats = OfflineStats(
            num_candidates=n_cands,
            num_measured=n_meas,
            build_seconds=time.perf_counter() - t0,
            backends=backends,
        )
        self._exec_cache: dict[tuple, _CacheEntry] = {}
        # DispatchStats increments are read-modify-writes; concurrent
        # same-bucket dispatch (the staging pool's whole point) would lose
        # counts without this.  Never held across a launch.
        self._stats_lock = threading.Lock()

    @property
    def workload(self) -> Workload:
        return self._wl

    @property
    def impl(self) -> str:
        """Executable implementation ("xla"/"pallas") — what the background
        calibrator builds candidate executables with, so measured costs
        price the SAME lowering the serving path launches."""
        return self._impl

    @property
    def vmem_limit_bytes(self) -> int:
        """The VMEM a Pallas executable may claim: the level-1 capacity
        the lattice sized every candidate tile against."""
        return self._hw.level(1).capacity_bytes

    # -- executable construction ------------------------------------------

    def _build_executable(self, sel: Selection, args: tuple) -> _CacheEntry:
        if faults.ACTIVE is not None:
            faults.ACTIVE.check("precompile")
        fn = self._wl.build_executable(
            sel, impl=self._impl, vmem_limit_bytes=self.vmem_limit_bytes
        )
        jfn = jax.jit(fn)
        t0 = time.perf_counter()
        warm = self._wl.example_args(sel, *args)
        # ONE AOT program per bucket (the same lower().compile() pattern the
        # serving driver uses for prefill): staging + masked kernel + no
        # in-program pads means this single artifact IS the whole dispatch.
        aot = jfn.lower(*warm).compile()
        aot_dtypes = tuple(
            jax.numpy.asarray(w).dtype for w in warm
        )
        return _CacheEntry(
            fn=jfn, compile_seconds=time.perf_counter() - t0,
            aot=aot, aot_dtypes=aot_dtypes,
            pool=_StagingPool(self._pool_cap),
        )

    def _exec_cache_key(self, sel: Selection, args: tuple) -> tuple:
        return (
            sel.bucket, sel.strategy.l1, sel.backend, self._impl,
            self._wl.exec_key(*args) if args else (),
        )

    def _entry_for(self, sel: Selection, args: tuple = ()) -> _CacheEntry:
        key = self._exec_cache_key(sel, args)
        entry = self._exec_cache.get(key)
        if entry is None:
            entry = self._build_executable(sel, args)
            self._exec_cache[key] = entry
        entry.hits += 1
        return entry

    # -- public API ---------------------------------------------------------

    def select(self, m: int) -> Selection:
        return self.selector.select(m)

    def precompile(
        self, m_max: int, *args, max_workers: int | None = None
    ) -> int:
        """Precompile every bucket reachable for M <= m_max (sample-free:
        the bucket set comes from the lattice, not from shape samples).

        Workloads whose executables specialize on outer dims beyond the
        bucket (``exec_key``, e.g. attention's batch/head counts) need
        representative call ``args`` — otherwise the warmed entries sit
        under a key real calls never hit.  Only the args' shapes matter.

        Missing buckets compile on a thread pool (XLA compilation releases
        the GIL); ``max_workers`` caps it, defaulting to min(8, cpu count).
        A failing bucket raises :class:`PrecompileError` naming the failing
        Selection — after every other bucket has drained and registered, so
        a retry after fixing the bad bucket recompiles nothing else.
        """
        sels = self.selector.selections_upto(m_max)
        pending: dict[tuple, Selection] = {}
        for sel in sels:
            key = self._exec_cache_key(sel, args)
            if key not in self._exec_cache and key not in pending:
                pending[key] = sel
        if pending:
            workers = min(
                max_workers or 8, os.cpu_count() or 1, len(pending)
            )
            if workers > 1:
                # Drain ALL futures, registering each success as it
                # completes, and only then raise for the first failure:
                # raising mid-drain would block in the executor's shutdown
                # anyway (no cancel) while discarding every in-flight build
                # that finishes after the failure — a retry would recompile
                # buckets that had already built fine.
                failed: tuple[Selection, Exception] | None = None
                with ThreadPoolExecutor(max_workers=workers) as pool:
                    futures = {
                        pool.submit(self._build_executable, sel, args): key
                        for key, sel in pending.items()
                    }
                    for fut in as_completed(futures):
                        key = futures[fut]
                        try:
                            self._exec_cache[key] = fut.result()
                        except Exception as e:
                            if failed is None:
                                failed = (pending[key], e)
                if failed is not None:
                    sel, e = failed
                    raise PrecompileError(self._wl.kind, sel, e) from e
            else:
                for key, sel in pending.items():
                    try:
                        self._exec_cache[key] = self._build_executable(
                            sel, args
                        )
                    except Exception as e:
                        raise PrecompileError(self._wl.kind, sel, e) from e
        return len(sels)

    def __call__(self, *args):
        """Dynamic-shape dispatch through the masked-tail staging contract.

        Select on the runtime extent, then launch the ONE fused per-bucket
        AOT program:

          * bucket-aligned extent — the call args are the program inputs
            directly: zero copies, one launch;
          * unaligned extent — dynamic args are staged into engine-owned,
            donated bucket buffers (O(true-size) writes, no allocation, no
            zero fill; the pad tail keeps stale bytes that the kernel masks
            via the runtime-extent scalar), then one launch, then the
            output slice back to the true extent.

        ``jnp.pad`` never runs on this path.  Calls arriving as tracers
        (inside an enclosing jit, e.g. serve's AOT prefill lowering) take
        the functional zero-pad reference path instead — XLA fuses it into
        the surrounding program, and engine-owned buffers must not be
        captured by a trace.

        A candidate that raises at executable build or launch walks the
        degradation ladder (``_degrade``): quarantine, re-select the
        next-best lattice candidate, retry up to ``max_retries``, then the
        XLA reference rung — the call still returns a correct result
        whenever any rung works.
        """
        m = self._wl.dynamic_extent(*args)
        sel = self._select_healthy(m)
        try:
            return self._dispatch(sel, args)
        except Exception as exc:
            return self._degrade(m, sel, args, exc)

    def _dispatch(self, sel: Selection, args: tuple):
        """One dispatch attempt at a fixed Selection (the ladder's rung
        body; exactly the pre-ladder dispatch path)."""
        wl = self._wl
        entry = self._entry_for(sel, args)
        st = self.dispatch_stats
        view = wl.stage_view(*args)
        if not self._staging:
            with self._stats_lock:
                st.calls += 1
            return self._call_padded(sel, entry, args, view)
        if any(isinstance(a, jax.core.Tracer) for a in view):
            with self._stats_lock:
                st.calls += 1
                st.traced_calls += 1
            return self._call_padded(sel, entry, args, view)
        scalars = wl.runtime_scalars(sel, *view)
        shapes = wl.staged_shapes(sel, *view)
        unaligned = [
            i for i, s in enumerate(shapes)
            if s is not None and view[i].shape != s
        ]
        if not unaligned:
            with self._stats_lock:
                st.calls += 1
                st.aligned_calls += 1
                st.launches += 1
            out = entry.run(*view, *scalars)
            return wl.finalize(sel, out, *args)
        # Check a buffer set out of the entry's pool: staging and the
        # launch run with NO entry-wide lock, so concurrent same-bucket
        # dispatches overlap instead of serializing (each set is private
        # to this call until released).
        need = {i: (shapes[i], view[i].dtype) for i in unaligned}
        bufs = entry.pool.acquire(need)
        staged = list(view)
        for i in unaligned:
            buf = _stage_into(bufs[i], view[i])
            bufs[i] = buf
            staged[i] = buf
        with self._stats_lock:
            st.calls += 1
            st.unaligned_calls += 1
            st.stage_copies += len(unaligned)
            st.launches += 1
            if wl.unstages:
                st.unstage_copies += 1
        try:
            out = entry.run(*staged, *scalars)
        finally:
            # Settle the staging-pool checkout on the failure path too: a
            # launch that raises (degradation ladder) must not strand the
            # buffer set — the staged buffers stay valid (the launch does
            # not donate them), so they go straight back into rotation.
            entry.pool.release(bufs)
        return wl.finalize(sel, out, *args)

    # -- degradation ladder (DESIGN.md §11) ---------------------------------

    @staticmethod
    def _qkey(sel: Selection) -> str:
        """The quarantine identity of a candidate: what failed is the
        (bucket, backend, tiling) triple — the executable the lattice
        produced — not the runtime extent that happened to trigger it."""
        return repr((sel.bucket, sel.backend, sel.strategy.tiles))

    def _select_healthy(self, m: int) -> Selection:
        """The table/argmin selection, skipping quarantined candidates.

        The quarantine set is empty on every healthy host, so the hot path
        pays one falsy check on top of the plain ``select``.
        """
        sel = self.selector.select(m)
        q = self._quarantined
        if q and self._qkey(sel) in q:
            healthy = self.selector.select_excluding(m, q, self._qkey)
            if healthy is not None:
                return healthy
        return sel

    def _quarantine(self, sel: Selection) -> bool:
        """Quarantine ``sel``; True if it was not already quarantined."""
        key = self._qkey(sel)
        if key in self._quarantined:
            return False
        with self._stats_lock:
            self.dispatch_stats.quarantined += 1
        self._quarantined.add(key)
        return True

    def _degrade(
        self, m: int, sel: Selection, args: tuple, exc: Exception
    ):
        """Walk the ladder after ``sel`` failed: quarantine it, re-select
        the next-best lattice candidate excluding quarantined entries,
        retry up to ``max_retries``, then run the XLA reference rung.

        Quarantine keys are persisted to the denylist only once a LOWER
        rung succeeds — evidence the failure was candidate-specific rather
        than a caller error (bad dtypes, shape mismatch) that every
        candidate would reproduce.  If even the reference rung fails, this
        call's quarantines are rolled back and the original exception
        propagates: nothing was learned about the candidates.
        """
        fresh = [sel] if self._quarantine(sel) else []
        for _ in range(self._max_retries):
            nxt = self.selector.select_excluding(
                m, self._quarantined, self._qkey
            )
            if nxt is None:
                break  # lattice exhausted: straight to the reference rung
            try:
                out = self._dispatch(nxt, args)
            except Exception as e:
                exc = e
                if self._quarantine(nxt):
                    fresh.append(nxt)
                continue
            self._persist_quarantines(fresh)
            return out
        try:
            out = self._fallback_dispatch(m, args)
        except Exception as e:
            with self._stats_lock:
                self.dispatch_stats.quarantined -= len(fresh)
            for t in fresh:
                self._quarantined.discard(self._qkey(t))
            raise e from exc
        self._persist_quarantines(fresh)
        return out

    def _persist_quarantines(self, fresh: list[Selection]) -> None:
        if self._denylist is None:
            return
        for t in fresh:
            self._denylist.add(self._sig_key, self._qkey(t))

    def _fallback_dispatch(self, m: int, args: tuple):
        """The last rung: a plain jitted XLA reference executable for the
        analytical selection's bucket, via the zero-pad reference path.
        No AOT entry, no staging buffers — nothing the failing rungs
        shared — and no fault hooks, so chaos plans cannot reach it."""
        wl = self._wl
        sel = self.selector.select(m)
        key = (
            "__xla_fallback__", sel.bucket, sel.strategy.l1,
            wl.exec_key(*args) if args else (),
        )
        entry = self._exec_cache.get(key)
        if entry is None:
            fn = wl.build_executable(sel, impl="xla")
            entry = _CacheEntry(fn=jax.jit(fn), compile_seconds=0.0)
            self._exec_cache[key] = entry
        entry.hits += 1
        with self._stats_lock:
            self.dispatch_stats.calls += 1
            self.dispatch_stats.fallbacks += 1
        return self._call_padded(sel, entry, args)

    def _call_padded(self, sel, entry, args, view=None) -> jax.Array:
        """The zero-pad reference path: functionally identical to staging
        (same fused executable, same extent scalars), with fresh padded
        allocations instead of engine-owned buffers.  Used for parity
        testing, tracer-context calls, and staging-disabled kernels."""
        wl = self._wl
        st = self.dispatch_stats
        if view is None:
            view = wl.stage_view(*args)
        scalars = wl.runtime_scalars(sel, *view)
        if not wl.supports_staging:
            # Legacy-contract workloads: prepare is the only bucket mapping
            # (it must be an identity for already-aligned extents).
            with self._stats_lock:
                st.padded_calls += 1
            out = entry.fn(*wl.prepare(sel, *view), *scalars)
            return wl.finalize(sel, out, *args)
        shapes = wl.staged_shapes(sel, *view)
        aligned = all(
            s is None or view[i].shape == s for i, s in enumerate(shapes)
        )
        if aligned:
            out = entry.fn(*view, *scalars)
        else:
            with self._stats_lock:
                st.padded_calls += 1
            out = entry.fn(*wl.prepare(sel, *view), *scalars)
        return wl.finalize(sel, out, *args)

    def call_padded(self, *args) -> jax.Array:
        """Public reference dispatch: the padded path end to end (select,
        zero-pad prepare, fused executable, finalize).  The staged hot path
        must be bit-identical to this — tests/test_staged_dispatch.py."""
        wl = self._wl
        sel = self.selector.select(wl.dynamic_extent(*args))
        entry = self._entry_for(sel, args)
        with self._stats_lock:
            self.dispatch_stats.calls += 1
        return self._call_padded(sel, entry, args)

    @property
    def cache_info(self) -> dict:
        return {
            "entries": len(self._exec_cache),
            "hits": sum(e.hits for e in self._exec_cache.values()),
            "compile_seconds": sum(
                e.compile_seconds for e in self._exec_cache.values()
            ),
        }

    @property
    def select_stats(self) -> dict:
        s = self.selector.stats
        return {
            "selects": s.selects,
            "table_hits": s.table_hits,
            "lru_hits": s.lru_hits,
            "argmin_misses": s.argmin_misses,
            "cache_hits": s.cache_hits,
            "mean_select_us": s.mean_select_us,
            "table_builds": s.table_builds,
            "table_build_seconds": s.table_build_seconds,
            "calibration_seconds": s.calibration_seconds,
            "table_swaps": s.table_swaps,
        }


def __getattr__(name: str):
    # Deprecation shims live with the public API (repro.vortex.compat) but
    # stay importable from their historical home; the import is deferred so
    # repro.core never pulls repro.vortex at module-import time (the vortex
    # package imports this module).
    if name in ("VortexEngine", "VortexGemm"):
        from repro.vortex import compat

        return getattr(compat, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
