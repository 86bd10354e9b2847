"""repro.vortex — the ONE public API over the sample-free pipeline.

Everything a caller does with Vortex goes through four ideas (DESIGN.md
§ Public API):

* **Handles** — :func:`compile` returns a :class:`CompiledOp`: one generic
  object per workload signature with ``__call__`` / ``precompile`` /
  ``select`` / ``bucket`` / ``stats``.  No per-operator engine methods.
* **Registry-driven ops** — :mod:`vortex.ops` exposes every
  ``@register_workload`` kind as ``vortex.ops.<kind>``; registering a
  workload is the ONLY step to get a served op (no engine edits).
* **Sessions** — an :class:`Engine` (configured by the frozen
  :class:`EngineConfig`) is installed per-context with :func:`use`;
  installation is contextvar-scoped: nestable, exception-safe,
  thread-isolated.  :func:`current_engine` resolves the ambient session
  (falling back to a lazy process default); :func:`installed_engine` is
  the opt-in variant model layers consult.
* **Deprecation shims** — the old surface (``VortexEngine.gemm/...``,
  ``VortexGemm``, ``layers.set_attention_engine``) delegates here and
  warns with :class:`VortexDeprecationWarning` (errors in tier-1 CI).

Quickstart::

    from repro import vortex
    from repro.vortex import Engine, EngineConfig

    y = vortex.ops.gemm(a, b)                       # default session
    with vortex.use(Engine(EngineConfig(hardware="tpu_v5e"))) as eng:
        op = vortex.compile("gemm", M=None, N=768, K=2304)
        op.precompile(4096)                          # warm every bucket
        y = op(a, b)                                 # bisect + cached exec
"""
from __future__ import annotations

import importlib
from typing import TYPE_CHECKING, Any

# Only stdlib-light leaves load eagerly: the session contextvar and the
# deprecation category.  Everything that pulls the core pipeline (Engine,
# handles, ops, the workload registry) resolves lazily via PEP 562 below,
# so broadly-imported modules (models/layers.py consults the session on
# every attention call) can `from repro.vortex import session` without
# dragging jax/numpy-heavy engine machinery into import time.
from repro.vortex._deprecation import VortexDeprecationWarning  # noqa: F401
from repro.vortex.session import (  # noqa: F401
    current_engine,
    default_engine,
    installed_engine,
    use,
)

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.vortex.engine import Engine
    from repro.vortex.handle import CompiledOp
    from repro.core.workloads import Workload

__all__ = [
    "CompiledOp",
    "Engine",
    "EngineConfig",
    "VortexDeprecationWarning",
    "WORKLOADS",
    "Workload",
    "compile",
    "current_engine",
    "default_engine",
    "installed_engine",
    "make_workload",
    "ops",
    "pow2_bucket",
    "register_workload",
    "use",
]

# name -> (module, attr); attr None = the module itself (vortex.ops).
_LAZY: dict[str, tuple[str, str | None]] = {
    "CompiledOp": ("repro.vortex.handle", "CompiledOp"),
    "Engine": ("repro.vortex.engine", "Engine"),
    "EngineConfig": ("repro.vortex.config", "EngineConfig"),
    "pow2_bucket": ("repro.vortex.engine", "pow2_bucket"),
    "ops": ("repro.vortex.ops", None),
    "WORKLOADS": ("repro.core.workloads", "WORKLOADS"),
    "Workload": ("repro.core.workloads", "Workload"),
    "make_workload": ("repro.core.workloads", "make_workload"),
    "register_workload": ("repro.core.workloads", "register_workload"),
}


def __getattr__(name: str):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    mod = importlib.import_module(mod_name)
    value = mod if attr is None else getattr(mod, attr)
    globals()[name] = value  # cache: next access skips __getattr__
    return value


def __dir__() -> list[str]:
    return sorted(set(globals()) | set(_LAZY))


def compile(
    workload: "Workload | str",
    *,
    engine: "Engine | None" = None,
    **params: Any,
) -> "CompiledOp":
    """Compile a workload signature on the ambient (or given) session.

    ``workload`` is a Workload instance or a registered kind name with the
    workload parameters as keywords::

        op = vortex.compile(GemmWorkload(M=None, N=768, K=2304))
        op = vortex.compile("attention", seq=None, head_dim=64)

    Sample-free: nothing about the dynamic extent is consulted here — the
    returned handle serves EVERY runtime extent from one scored lattice.
    """
    eng = engine if engine is not None else current_engine()
    return eng.compile(workload, **params)
