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
    "LazyBucket",
    "OfflineStats",
    "PrecompileError",
    "VortexKernel",
    "VortexGemm",
    "VortexEngine",
    "lazy_map",
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

    ``forwarded`` counts :class:`LazyBucket` operands whose buffer entered
    the next program directly — an op boundary crossed with NO unstage and
    NO restage; ``realize_slices`` counts deferred output slices forced by
    a non-engine consumer (``LazyBucket.realize``).  Whole-chain boundary
    traffic is exactly ``stage_copies + unstage_copies + realize_slices``.

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
    forwarded: int = 0
    realize_slices: int = 0
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


class LazyBucket:
    """A bucket-shaped engine result that has NOT been sliced to its true
    extent: ``buffer`` is the raw per-bucket program output (rows past
    ``extent`` along ``axis`` hold garbage the masked-tail contract never
    reads), ``extent`` is the true dynamic size.

    ``.shape`` reports the TRUE shape, so workload ``bind``/``dispatch_key``
    /``dynamic_extent`` hooks (which only read ``.shape``/``.dtype``) treat
    a handle exactly like the realized array.  Realization — the deferred
    output slice — happens once, lazily: when a non-engine consumer forces
    it via :meth:`realize` or the ``__jax_array__`` protocol.  An engine
    dispatch whose operand is a handle in a compatible bucket skips it
    entirely and consumes ``buffer`` directly (``DispatchStats.forwarded``).

    Handles are eager-only plumbing between dispatches; they are not pytree
    leaves and must not cross a ``jit`` boundary unrealized.
    """

    __slots__ = ("buffer", "extent", "axis", "_stats", "_lock", "_realized")

    def __init__(self, buffer, extent, axis, stats=None, lock=None):
        self.buffer = buffer
        self.extent = int(extent)
        self.axis = axis
        self._stats = stats
        self._lock = lock
        self._realized = None

    # -- array-protocol surface (what shape-reading hooks consume) ---------

    @property
    def shape(self) -> tuple:
        s = list(self.buffer.shape)
        s[self.axis] = self.extent
        return tuple(s)

    @property
    def dtype(self):
        return self.buffer.dtype

    @property
    def ndim(self) -> int:
        return self.buffer.ndim

    @property
    def padded_extent(self) -> int:
        """The bucket size the buffer is shaped to along ``axis``."""
        return self.buffer.shape[self.axis]

    @property
    def is_aligned(self) -> bool:
        return self.padded_extent == self.extent

    def _count_slice(self) -> None:
        if self._stats is not None:
            if self._lock is not None:
                with self._lock:
                    self._stats.realize_slices += 1
            else:
                self._stats.realize_slices += 1

    def realize(self) -> jax.Array:
        """The true-extent array (the deferred unstage).  Identity for an
        aligned bucket; otherwise ONE counted slice, cached so repeated
        forcing pays once."""
        if self._realized is None:
            if self.is_aligned:
                self._realized = self.buffer
            else:
                self._realized = jax.lax.slice_in_dim(
                    self.buffer, 0, self.extent, axis=self.axis
                )
                self._count_slice()
        return self._realized

    def __jax_array__(self) -> jax.Array:
        return self.realize()

    def rewrap(self, buffer, extent=None, axis=None) -> "LazyBucket":
        """A new handle over ``buffer`` sharing this handle's copy
        accounting — for extent-preserving reshapes/transposes between
        dispatches (split/merge heads, flattening batch into rows)."""
        return LazyBucket(
            buffer,
            self.extent if extent is None else extent,
            self.axis if axis is None else axis,
            self._stats,
            self._lock,
        )

    def map(self, fn) -> "LazyBucket":
        """Apply a ROW-LOCAL ``fn`` (output row i depends only on input row
        i along ``axis``) to the raw buffer: garbage tail rows stay confined
        past ``extent``.  The handle's bucket geometry must survive."""
        out = fn(self.buffer)
        if out.shape[self.axis] != self.padded_extent:
            raise ValueError(
                f"map changed the bucket axis: {self.padded_extent} -> "
                f"{out.shape[self.axis]}"
            )
        return self.rewrap(out)

    def clamp(self, padded: int) -> "LazyBucket":
        """This handle re-bucketed to ``padded`` rows along ``axis`` (true
        extent unchanged).  Identity when already that size; otherwise one
        counted boundary slice — how chain drivers pin a dispatch output
        that came back in a larger bucket to the chain's width."""
        if self.padded_extent == padded:
            return self
        if padded < self.extent:
            raise ValueError(
                f"cannot clamp below the true extent: {padded} < "
                f"{self.extent}"
            )
        buf = jax.lax.slice_in_dim(self.buffer, 0, padded, axis=self.axis)
        self._count_slice()
        return self.rewrap(buf)

    def __repr__(self) -> str:
        return (
            f"LazyBucket(shape={self.shape}, padded_extent="
            f"{self.padded_extent}, axis={self.axis}, dtype={self.dtype})"
        )


def lazy_map(fn, *xs):
    """Apply an elementwise/row-local ``fn`` across arrays and LazyBuckets
    without realizing: the chain glue for the non-engine ops between
    dispatches (norms, residual adds, activations).

    ``fn`` must be ROW-LOCAL along the handles' bucket axis.  All handle
    operands must share (axis, padded_extent) — then ``fn`` runs on the raw
    buffers and the result is re-wrapped (extent = min of the operands', so
    any row past a partial operand's extent is conservatively garbage).
    Incompatible handles fall back to realizing everything (counted).
    Plain-array operands must broadcast against the BUFFER shape (e.g.
    per-feature norm weights).  With no handle operands this is ``fn(*xs)``.
    """
    handles = [x for x in xs if isinstance(x, LazyBucket)]
    if not handles:
        return fn(*xs)
    ref = handles[0]
    if any(
        h.axis != ref.axis or h.padded_extent != ref.padded_extent
        for h in handles[1:]
    ):
        return fn(
            *(x.realize() if isinstance(x, LazyBucket) else x for x in xs)
        )
    out = fn(*(x.buffer if isinstance(x, LazyBucket) else x for x in xs))
    if out.shape[ref.axis] != ref.padded_extent:
        raise ValueError(
            "lazy_map fn changed the bucket axis: "
            f"{ref.padded_extent} -> {out.shape[ref.axis]}"
        )
    return ref.rewrap(out, extent=min(h.extent for h in handles))


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

    def __call__(self, *args, lazy: bool = False):
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

        :class:`LazyBucket` operands at positions the workload declares in
        ``consumes_staged`` forward their bucket buffer into the program
        directly (``_call_forwarded``): no unstage of the producer, no
        restage here when the buckets agree.  Handles at any other
        position realize first (one counted slice).  With ``lazy=True``
        the output is returned as a LazyBucket instead of being finalized
        — best-effort: reference-path calls (tracers, staging disabled)
        still return plain finalized arrays, so chain drivers must accept
        both.

        A candidate that raises at executable build or launch walks the
        degradation ladder (``_degrade``): quarantine, re-select the
        next-best lattice candidate, retry up to ``max_retries``, then the
        XLA reference rung — the call still returns a correct result
        whenever any rung works.
        """
        wl = self._wl
        if any(isinstance(a, LazyBucket) for a in args):
            fwd = wl.consumes_staged if self._staging else {}
            args = tuple(
                a.realize()
                if isinstance(a, LazyBucket) and i not in fwd else a
                for i, a in enumerate(args)
            )
            handles = {
                i for i, a in enumerate(args) if isinstance(a, LazyBucket)
            }
            if handles:
                return self._call_forwarded(args, handles, lazy)
        m = wl.dynamic_extent(*args)
        sel = self._select_healthy(m)
        try:
            return self._dispatch(sel, m, args, lazy)
        except Exception as exc:
            return self._degrade(m, sel, args, lazy, exc)

    def _dispatch(self, sel: Selection, m: int, args: tuple, lazy: bool):
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
        lazy_out = lazy and wl.staged_out_axis is not None
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
            if lazy_out:
                return LazyBucket(
                    out, m, wl.staged_out_axis, st, self._stats_lock
                )
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
            # A lazy output defers the unstage slice: it is only paid (and
            # counted, as realize_slices) if a non-engine consumer forces
            # the handle.
            if wl.unstages and not lazy_out:
                st.unstage_copies += 1
        try:
            out = entry.run(*staged, *scalars)
        finally:
            # Settle the staging-pool checkout on the failure path too: a
            # launch that raises (degradation ladder) must not strand the
            # buffer set — the staged buffers stay valid (the launch does
            # not donate them), so they go straight back into rotation.
            entry.pool.release(bufs)
        if lazy_out:
            return LazyBucket(out, m, wl.staged_out_axis, st,
                              self._stats_lock)
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
        self, m: int, sel: Selection, args: tuple, lazy: bool,
        exc: Exception,
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
                out = self._dispatch(nxt, m, args, lazy)
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

    def _call_forwarded(self, args: tuple, handles: set, lazy: bool):
        """Bucket-to-bucket dispatch: LazyBucket operands hand their raw
        bucket buffers to the program, the true extents ride in the runtime
        scalars.  Selection happens at the PADDED extent (the buffers' own
        bucket), so a producer and consumer sharing a bucket forward with
        zero copies; a handle whose buffer does not match this selection's
        staged shape restages (counted stage copy) — correct either way,
        because staged tails are garbage by contract and every mask scalar
        is computed from the TRUE shapes.

        ``consumes_staged`` positions are call-arg positions; only
        identity-``stage_view`` workloads declare any, so view index ==
        arg index throughout.
        """
        wl = self._wl
        st = self.dispatch_stats

        def realize_all():
            flat = tuple(
                a.realize() if isinstance(a, LazyBucket) else a for a in args
            )
            return self(*flat, lazy=lazy)

        raw = tuple(
            a.buffer if isinstance(a, LazyBucket) else a for a in args
        )
        true = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            if isinstance(a, LazyBucket) else a
            for a in args
        )
        view = wl.stage_view(*raw)
        if any(isinstance(a, jax.core.Tracer) for a in view):
            return realize_all()  # forwarding is eager-only
        try:
            m_disp = wl.dynamic_extent(*raw)
            m_true = wl.dynamic_extent(*true)
        except AssertionError:
            # Mixed handle/plain operands whose padded vs true extents the
            # workload refuses to reconcile (attention's q/kv seq match).
            return realize_all()
        sel = self.selector.select(m_disp)
        entry = self._entry_for(sel, raw)
        scalars = wl.runtime_scalars(sel, *wl.stage_view(*true))
        shapes = wl.staged_shapes(sel, *view)
        unaligned = [
            i for i, s in enumerate(shapes)
            if s is not None and view[i].shape != s
        ]
        lazy_out = lazy and wl.staged_out_axis is not None
        slices_out = (
            wl.unstages and not lazy_out and wl.dynamic_bucket(sel) != m_true
        )
        if not unaligned:
            with self._stats_lock:
                st.calls += 1
                st.aligned_calls += 1
                st.launches += 1
                st.forwarded += len(handles)
                if slices_out:
                    st.unstage_copies += 1
            out = entry.run(*view, *scalars)
        else:
            need = {i: (shapes[i], view[i].dtype) for i in unaligned}
            bufs = entry.pool.acquire(need)
            staged = list(view)
            for i in unaligned:
                # Restaging a handle writes its WHOLE buffer — garbage tail
                # included — into the larger bucket; safe, since the
                # scalars above mask at the true extents.
                buf = _stage_into(bufs[i], view[i])
                bufs[i] = buf
                staged[i] = buf
            with self._stats_lock:
                st.calls += 1
                st.unaligned_calls += 1
                st.stage_copies += len(unaligned)
                st.launches += 1
                st.forwarded += len(handles - set(unaligned))
                if slices_out:
                    st.unstage_copies += 1
            out = entry.run(*staged, *scalars)
            entry.pool.release(bufs)
        if lazy_out:
            return LazyBucket(
                out, m_true, wl.staged_out_axis, st, self._stats_lock
            )
        return wl.finalize(sel, out, *true)

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
