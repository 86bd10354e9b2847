"""Engine: a session over many workloads, served from one cache hierarchy.

One Engine = one :class:`~repro.vortex.config.EngineConfig` + one
scored-lattice cache + one compiled-kernel table + one raw-tuple dispatch
table.  It has NO per-operator entry points: every registered workload kind
(``@register_workload``) is reachable through :meth:`compile` /
:meth:`dispatch` — and therefore through ``vortex.ops.<kind>`` — with zero
engine edits, which is the whole point of the registry-driven API
(DESIGN.md § Public API).

Engines are installed per-context with :func:`repro.vortex.use` (contextvar
scoped: nestable, exception-safe, thread-isolated); model layers and ops
pick up the innermost installed engine.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any

from repro.core.analyzer import (
    Profiler,
    ScoredLattice,
    TableProfiler,
    WallClockProfiler,
)
from repro.core.engine import OfflineStats, VortexKernel
from repro.core.hardware import get_hardware, resolve_platform
from repro.core.workloads import WORKLOADS, Workload, make_workload
from repro.vortex.config import EngineConfig
from repro.vortex.handle import CompiledOp

__all__ = ["Engine", "pow2_bucket"]


def pow2_bucket(n: int) -> int:
    """Power-of-two bucket for auxiliary outer dims (serving batch size).

    The primary dynamic extent is bucketed by the lattice (CompiledOp.
    bucket); dims that merely multiply it (the request batch) are quantized
    to pow2 so the executable cache stays small with <= 2x waste on that
    factor alone — quantizing them to the sublane granularity too would
    double-pad.
    """
    p = 1
    while p < n:
        p *= 2
    return p


class Engine:
    """A scoped compilation/serving session over the workload registry.

    ``config`` may be an :class:`EngineConfig`, a hardware name string, or
    None (host-CPU defaults); keyword ``overrides`` replace individual
    config fields either way.  Signatures are built lazily but *without*
    any dependence on the dynamic dim — first use of a new signature builds
    its lattice once, after which every runtime extent is served from the
    same scored lattice (sample-free across all dynamic shapes).  Workloads
    whose lattice inputs coincide (e.g. attention signatures differing only
    in masking flags) share scored lattices through one engine-wide cache.
    """

    def __init__(
        self,
        config: EngineConfig | str | None = None,
        *,
        profiler: Profiler | None = None,
        **overrides: Any,
    ):
        if config is None:
            config = EngineConfig(**overrides)
        else:
            if isinstance(config, str):
                config = EngineConfig(hardware=config)
            if overrides:
                config = dataclasses.replace(config, **overrides)
        if config.hardware is None or config.impl is None:
            platform = resolve_platform()
            config = dataclasses.replace(
                config,
                hardware=config.hardware or platform.hardware.name,
                impl=config.impl or (
                    "pallas" if platform.native_pallas else "xla"
                ),
            )
        self.config = config
        self._hw = get_hardware(config.hardware)
        if profiler is None:
            profiler = (
                WallClockProfiler() if config.hardware == "host_cpu"
                else TableProfiler(self._hw)
            )
        self._profiler = profiler
        empirical = config.empirical_levels
        if empirical is None:
            # Paper defaults (Table 7): E:L0 on CPU; E:L0,L1 on GPU-class HW.
            empirical = (0,) if config.hardware == "host_cpu" else (0, 1)
        self._empirical_levels = tuple(empirical)
        self._kernels: dict[tuple, VortexKernel] = {}
        self._scored_cache: dict[tuple, ScoredLattice] = {}
        # Zero-rebuild hot path: raw call-site tuples -> compiled kernel.
        # Steady-state dispatch hashes a tuple of ints (shapes/flags
        # straight off the arrays, Workload.dispatch_key) instead of
        # constructing a Workload dataclass and hashing its dataclass
        # signature on every call.
        self._dispatch: dict[tuple, VortexKernel] = {}
        # Kernel builds are expensive (lattice sweep); serialize them so two
        # threads first touching the same signature don't build it twice.
        self._build_lock = threading.Lock()
        # Background calibrator (core/calibrate.py), created on first use
        # when config.calibration != "off".  Guarded by _build_lock.
        self._calibrator = None
        # Persistent candidate denylist shared by every kernel of this
        # engine (degradation ladder, DESIGN.md §11).  Created lazily under
        # _build_lock; None when persistence is disabled.
        self._denylist = None

    @property
    def calibrator(self):
        """The background :class:`~repro.core.calibrate.Calibrator` for
        this engine's kernels — None when ``config.calibration == "off"``
        (the default), in which case nothing calibration-related is ever
        constructed and serving is bit-identical to an engine predating
        the feature."""
        cfg = self.config
        if cfg.calibration == "off":
            return None
        if self._calibrator is None:
            with self._build_lock:
                if self._calibrator is None:
                    from repro.core.calibrate import (
                        CalibrationPolicy,
                        Calibrator,
                    )

                    self._calibrator = Calibrator(
                        lambda: list(self._kernels.values()),
                        CalibrationPolicy(
                            mode=cfg.calibration,
                            top_k=cfg.calibration_top_k,
                            budget_s=cfg.calibration_budget_s,
                            cache_dir=cfg.calibration_cache_dir,
                        ),
                    )
        return self._calibrator

    @property
    def hardware(self):
        return self._hw

    # -- session scoping ----------------------------------------------------

    def use(self):
        """Install this engine for the current context: shorthand for
        ``vortex.use(engine)`` (nestable, exception-safe, thread-local)."""
        from repro.vortex.session import use

        return use(self)

    # -- workload plumbing --------------------------------------------------

    def kernel_for(self, wl: Workload) -> VortexKernel:
        """The compiled kernel serving ``wl``'s signature (built lazily)."""
        key = wl.signature
        kern = self._kernels.get(key)
        built = False
        if kern is None:
            with self._build_lock:
                kern = self._kernels.get(key)
                if kern is None:
                    built = True
                    cfg = self.config
                    kern = VortexKernel(
                        self._hw,
                        wl,
                        profiler=self._profiler,
                        empirical_levels=self._empirical_levels,
                        backends=cfg.backends,
                        num_cores=cfg.num_cores,
                        impl=cfg.impl,
                        scored_cache=self._scored_cache,
                        table_m_max=cfg.table_m_max,
                        table_extend_limit=cfg.table_extend_limit,
                        staging=cfg.staging,
                        staging_pool_cap=cfg.staging_pool_cap,
                        max_retries=cfg.max_kernel_retries,
                        denylist=self._denylist_store(),
                    )
                    self._kernels[key] = kern
        if built and self.config.calibration == "eager-warmup":
            # Warm synchronously at build time: persisted tables load by
            # hardware fingerprint (zero re-measurements on restart);
            # anything not on disk is measured now, before serving.
            cal = self.calibrator
            cal.load()
            if cal.pending():
                cal.run()
        return kern

    def _denylist_store(self):
        """The engine's persistent quarantine store (or None when
        ``config.denylist_persist`` is off).  Constructed HERE rather than
        inside core/engine.py so core.engine never imports core.denylist
        (which imports core.calibrate, which imports core.engine)."""
        cfg = self.config
        if not cfg.denylist_persist:
            return None
        if self._denylist is None:
            from repro.core.denylist import DenylistStore

            self._denylist = DenylistStore(
                self._hw,
                cfg.backends or tuple(self._hw.backends),
                cfg.impl,
                cache_dir=cfg.calibration_cache_dir,
            )
        return self._denylist

    def compile(
        self, workload: Workload | str, **params: Any
    ) -> CompiledOp:
        """The CompiledOp handle for a workload signature.

        ``workload`` is either a Workload instance or a registered kind
        name with the workload parameters as keywords::

            op = engine.compile(GemmWorkload(M=None, N=768, K=2304))
            op = engine.compile("gemm", M=None, N=768, K=2304)

        With ``config.precompile_m_max > 0`` the op's executable buckets
        are warmed eagerly (workloads without outer-dim specialization
        only; the rest need representative args, see CompiledOp.precompile).
        """
        if isinstance(workload, str):
            workload = make_workload(workload, **params)
        elif params:
            raise TypeError(
                "workload parameters are only accepted with a kind name, "
                f"not alongside a Workload instance: {sorted(params)}"
            )
        known = self._kernels.get(workload.signature) is not None
        op = CompiledOp(self, self.kernel_for(workload))
        pm = self.config.precompile_m_max
        if pm > 0 and not known and not self._exec_specialized(workload):
            op.precompile(pm)
        return op

    @staticmethod
    def _exec_specialized(wl: Workload) -> bool:
        """True when ``wl``'s executables key on outer dims of the call
        args (overridden ``exec_key``) — eager precompile without
        representative args would warm keys real calls never hit."""
        return type(wl).exec_key is not Workload.exec_key

    # -- registry-driven dispatch -------------------------------------------

    def op_kernel(self, kind: str, args: tuple, kwargs: dict) -> VortexKernel:
        """Resolve a call site to its compiled kernel through the registry:
        raw-tuple lookup on the hot path, Workload.bind on first use."""
        cls = WORKLOADS[kind]
        dkey = cls.dispatch_key(*args, **kwargs)
        if dkey is None:
            return self.kernel_for(cls.bind(*args, **kwargs))
        key = (kind,) + dkey
        kern = self._dispatch.get(key)
        if kern is None:
            kern = self.kernel_for(cls.bind(*args, **kwargs))
            self._dispatch[key] = kern
        return kern

    def dispatch(self, kind: str, *args: Any, **kwargs: Any):
        """Serve one call of a registered workload kind: ``args`` are the
        runtime arrays, ``kwargs`` the workload parameters (flags/strides).
        This is what ``vortex.ops.<kind>(...)`` invokes."""
        return self.op_kernel(kind, args, kwargs)(*args)

    # -- introspection ------------------------------------------------------

    def precompile(self, wl: Workload, m_max: int, *args) -> int:
        """Precompile all buckets of ``wl`` reachable up to ``m_max``.
        Pass representative call ``args`` for workloads with outer-dim
        executable specialization (attention: any q/k/v with the serving
        batch/head layout)."""
        return self.kernel_for(wl).precompile(m_max, *args)

    def offline_stats(self) -> OfflineStats:
        # Snapshot: another serving thread's first-touch dispatch may
        # insert a kernel while we aggregate.
        stats = [k.offline_stats for k in list(self._kernels.values())]
        return OfflineStats(
            num_candidates=sum(s.num_candidates for s in stats),
            num_measured=sum(s.num_measured for s in stats),
            build_seconds=sum(s.build_seconds for s in stats),
            backends=stats[0].backends if stats else (),
        )

    def stats(self) -> dict[str, dict]:
        """Per-workload-kind serving stats: selection overhead and executable
        cache behaviour (what benchmarks/bench_workloads.py reports)."""
        out: dict[str, dict] = {}
        for kernel in list(self._kernels.values()):  # snapshot (threads)
            kind = kernel.workload.kind
            agg = out.setdefault(
                kind,
                {
                    "signatures": 0, "selects": 0, "select_table_hits": 0,
                    "select_lru_hits": 0, "select_argmin_misses": 0,
                    "select_cache_hits": 0, "select_us_sum": 0.0,
                    "table_entries": 0, "table_build_s": 0.0,
                    "calibration_seconds": 0.0, "table_swaps": 0,
                    "exec_entries": 0, "exec_hits": 0,
                    "compile_seconds": 0.0,
                    # Hot-path copy/launch accounting (DispatchStats): the
                    # padding-free contract is checkable from here — an
                    # unaligned call is exactly one launch plus its
                    # staging/unstaging boundary copies, never a jnp.pad.
                    "calls": 0, "launches": 0,
                    "aligned_calls": 0, "unaligned_calls": 0,
                    "stage_copies": 0, "unstage_copies": 0,
                    "padded_calls": 0, "traced_calls": 0,
                    "fallbacks": 0, "quarantined": 0,
                },
            )
            sstats = kernel.selector.stats
            cinfo = kernel.cache_info
            table = kernel.selector.table_if_built
            agg["signatures"] += 1
            agg["selects"] += sstats.selects
            agg["select_table_hits"] += sstats.table_hits
            agg["select_lru_hits"] += sstats.lru_hits
            agg["select_argmin_misses"] += sstats.argmin_misses
            agg["select_cache_hits"] += sstats.cache_hits
            agg["select_us_sum"] += sstats.select_seconds * 1e6
            agg["table_entries"] += len(table) if table is not None else 0
            agg["table_build_s"] += sstats.table_build_seconds
            agg["calibration_seconds"] += sstats.calibration_seconds
            agg["table_swaps"] += sstats.table_swaps
            agg["exec_entries"] += cinfo["entries"]
            agg["exec_hits"] += cinfo["hits"]
            agg["compile_seconds"] += cinfo["compile_seconds"]
            for key, val in kernel.dispatch_stats.as_dict().items():
                agg[key] += val
        # Engine-level calibration section — ALWAYS present, so stats
        # consumers need no feature detection.  NOTE: not a per-kind dict;
        # iterating kinds must skip this key.
        cal = self.calibrator  # lazily constructs when calibration is on
        out["calibration"] = (
            cal.stats() if cal is not None
            else {"enabled": False, "mode": "off"}
        )
        return out

    def __repr__(self) -> str:
        return (
            f"Engine({self.config!r}, kernels={len(self._kernels)}, "
            f"dispatch_keys={len(self._dispatch)})"
        )
