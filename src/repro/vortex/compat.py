"""Deprecation shims for the pre-`repro.vortex` public surface.

Importable from their historical home (``repro.core.engine`` re-exports
via module ``__getattr__``).  The shims are THIN: they delegate to exactly
the registry-driven machinery the new API uses, so outputs are
bit-identical and the dispatch/executable cache keys are the same — a
caller migrating call-site by call-site never double-compiles.

Deprecation policy (DESIGN.md § Public API): shims warn with
:class:`VortexDeprecationWarning` for one release cycle; tier-1 CI turns
that category into an error so internal callers cannot regress onto them.
"""
from __future__ import annotations

from typing import Any

import jax

from repro.core.analyzer import Profiler
from repro.core.engine import VortexKernel
from repro.core.hardware import HardwareSpec
from repro.core.workloads import GemmWorkload, Workload
from repro.vortex._deprecation import warn_deprecated
from repro.vortex.config import EngineConfig
from repro.vortex.engine import Engine

__all__ = ["VortexEngine", "VortexGemm"]


class VortexEngine(Engine):
    """Deprecated per-operator face of :class:`repro.vortex.Engine`.

    The engine itself lives on; what is deprecated is the hard-coded
    one-method-per-kind surface (``gemm``/``attention``/``conv2d``) — use
    ``vortex.ops.<kind>`` / ``engine.dispatch(kind, ...)``, which serve
    ANY registered workload with no engine edits.
    """

    def __init__(
        self,
        hardware: str = "host_cpu",
        profiler: Profiler | None = None,
        empirical_levels: tuple[int, ...] | None = None,
        backends: tuple[str, ...] | None = None,
        impl: str = "xla",
        num_cores: int = 1,
    ):
        super().__init__(
            EngineConfig(
                hardware=hardware,
                backends=backends,
                impl=impl,
                num_cores=num_cores,
                empirical_levels=empirical_levels,
            ),
            profiler=profiler,
        )

    # -- deprecated per-op entry points ------------------------------------

    def gemm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """C[M,N] = A[M,K] @ B[K,N] with dynamic M."""
        warn_deprecated("VortexEngine.gemm", "vortex.ops.gemm")
        return self.dispatch("gemm", a, b)

    def attention(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        *,
        causal: bool = True,
        window: int | None = None,
        softcap: float | None = None,
    ) -> jax.Array:
        """Flash attention with dynamic sequence length (causal only)."""
        warn_deprecated("VortexEngine.attention", "vortex.ops.attention")
        return self.dispatch(
            "attention", q, k, v, causal=causal, window=window,
            softcap=softcap,
        )

    def conv2d(
        self, x: jax.Array, w: jax.Array, *, stride: int = 1
    ) -> jax.Array:
        """Conv2D (VALID): x (b, h, w, cin); w (kh, kw, cin, cout)."""
        warn_deprecated("VortexEngine.conv2d", "vortex.ops.conv2d")
        return self.dispatch("conv2d", x, w, stride=stride)

    def gemm_for(self, n: int, k: int) -> VortexKernel:
        warn_deprecated(
            "VortexEngine.gemm_for", 'engine.compile("gemm", ...).kernel'
        )
        return self.kernel_for(GemmWorkload(M=None, N=n, K=k))


class VortexGemm(VortexKernel):
    """Deprecated name for a GEMM-bound :class:`VortexKernel`.

    Exactly VortexKernel over a GemmWorkload — kept so old GEMM-only
    callers (serving scripts, notebooks) keep importing; new code uses
    ``vortex.compile(GemmWorkload(...))`` or VortexKernel directly.
    """

    def __init__(self, hw: HardwareSpec, wl: Workload, *args: Any, **kw: Any):
        warn_deprecated(
            "VortexGemm", "vortex.compile(GemmWorkload(...)) or VortexKernel"
        )
        super().__init__(hw, wl, *args, **kw)
