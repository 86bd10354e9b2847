"""EngineConfig: the one frozen value that fully describes an Engine.

Everything an :class:`~repro.vortex.Engine` session needs — target
hardware, compute backends, executable implementation, selection-table
sizing, precompile policy — lives here, so engines are reproducible from a
single hashable value and serving harnesses can log/compare them.  The
profiler is the one deliberate exception (a live object measuring the host;
pass it to ``Engine`` directly).
"""
from __future__ import annotations

import dataclasses

__all__ = ["EngineConfig"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Frozen description of one engine session.

    * ``hardware`` — a :func:`repro.core.hardware.get_hardware` name; the
      lattice is generated for THIS target even when executing on a host
      (serving uses ``tpu_v5e`` buckets on the CPU so executables dedupe
      the same way they would on the chip).  None derives it when the
      Engine is built: the attached TPU's spec
      (:func:`~repro.core.hardware.resolve_platform`), ``host_cpu`` on
      the CPU.
    * ``backends`` — compute backends to score (None = all the hardware
      declares, e.g. MXU + VPU; the selector picks per shape, Fig. 16).
    * ``impl`` — executable implementation: ``"xla"`` (flat JAX ops) or
      ``"pallas"`` (Vortex-tiled kernels).  None derives it from the
      platform when the :class:`~repro.vortex.Engine` is built: Pallas
      compiled natively on a TPU, XLA on the CPU.  Pallas asked for on the
      CPU runs in interpret mode; a TPU never interprets.
    * ``empirical_levels`` — hierarchy levels the hybrid analyzer measures
      empirically (None = paper defaults, Table 7: level 0 on CPU, levels
      0-1 on accelerator-class hardware; ``()`` = fully analytical).
    * ``table_m_max`` / ``table_extend_limit`` — initial coverage and
      doubling ceiling of the offline-materialized selection table
      (selection_table.py); 0 disables the table (argmin + LRU only).
    * ``precompile_m_max`` — when > 0, compiling an op through this engine
      eagerly warms every executable bucket reachable for extents up to
      this value (only for workloads whose executables are not specialized
      on outer dims — those need representative args, see
      ``CompiledOp.precompile``).
    * ``staging`` — serve unaligned extents through the masked-tail staging
      hot path (engine-owned donated bucket buffers + one fused AOT launch,
      DESIGN.md §4).  False forces every call onto the zero-pad reference
      path — a debugging/parity knob, not a serving configuration.
    * ``staging_pool_cap`` — LRU bound on the staging-buffer sets each
      executable entry retains (``_StagingPool``): a release beyond the cap
      evicts the least-recently-used idle set, so burst concurrency can't
      pin device memory forever.  0 retains nothing (every unaligned call
      allocates transient buffers); in-flight sets are never evicted.
    * ``calibration`` — background measurement-refined tables (DESIGN.md
      §10): ``"off"`` (default; the serving path is bit-identical to an
      uncalibrated engine), ``"on-idle"`` (the continuous scheduler
      donates budgeted slices when its admission queue is empty), or
      ``"eager-warmup"`` (each kernel is calibrated — persisted tables
      loaded from disk first — as it is built).
    * ``calibration_top_k`` / ``calibration_budget_s`` — how many
      analytically-ranked candidates to measure per bucket, and the
      wall-clock bound of ONE donated idle slice.
    * ``calibration_cache_dir`` — where calibrated tables persist, keyed
      by hardware fingerprint (None = ``$VORTEX_CACHE_DIR`` or
      ``~/.cache/vortex``; never inside the repo).
    * ``max_kernel_retries`` — degradation-ladder depth (DESIGN.md §11):
      how many next-best lattice candidates a dispatch re-selects after
      the chosen candidate fails at precompile/launch, before falling
      back to the XLA reference rung.  0 = straight to the reference.
    * ``denylist_persist`` — persist quarantined candidates next to the
      calibration cache (``<fingerprint>.deny.json``) so restarts skip
      known-bad candidates without re-failing them; False keeps the
      quarantine in-memory only (tests, hermetic runs).
    """

    hardware: str | None = None
    backends: tuple[str, ...] | None = None
    impl: str | None = None
    num_cores: int = 1
    empirical_levels: tuple[int, ...] | None = None
    table_m_max: int = 4096
    table_extend_limit: int = 1 << 17
    precompile_m_max: int = 0
    staging: bool = True
    staging_pool_cap: int = 4
    calibration: str = "off"
    calibration_top_k: int = 3
    calibration_budget_s: float = 0.25
    calibration_cache_dir: str | None = None
    max_kernel_retries: int = 2
    denylist_persist: bool = True

    def __post_init__(self) -> None:
        if self.impl not in (None, "xla", "pallas"):
            raise ValueError(
                f"impl must be 'xla', 'pallas' or None, got {self.impl!r}"
            )
        if self.max_kernel_retries < 0:
            raise ValueError(
                f"max_kernel_retries must be >= 0, "
                f"got {self.max_kernel_retries}"
            )
        if self.backends is not None:
            object.__setattr__(self, "backends", tuple(self.backends))
        if self.empirical_levels is not None:
            object.__setattr__(
                self, "empirical_levels", tuple(self.empirical_levels)
            )
        if self.calibration not in ("off", "on-idle", "eager-warmup"):
            raise ValueError(
                f"calibration must be 'off', 'on-idle' or 'eager-warmup', "
                f"got {self.calibration!r}"
            )
