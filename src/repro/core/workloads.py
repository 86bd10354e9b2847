"""Workload protocol + registry: the workload-generic face of the pipeline.

The paper's central claim (§4) is that ONE hardware-hierarchized strategy
space serves *all* dynamic-shape tensor programs.  This module is where a
tensor program declares everything the pipeline needs to know about it:

  * its axes and which of them are dynamic (unknown until runtime),
  * its rKernel program (rkernel.py metadata, per hardware level),
  * its per-tile footprint / FLOP / traffic model (consumed by the candidate
    generator's ``InitCands`` capacity checks and by the Eq. 2-4 cost model),
  * how a runtime shape maps onto the (m, n, k) contraction view, and
  * a backend-kernel builder that turns a runtime :class:`Selection` into an
    executable (XLA or Pallas).

``generate_lattice`` (candidates.py), :class:`HybridAnalyzer` (analyzer.py),
``runtime_costs`` (cost_model.py), :class:`RuntimeSelector` (selector.py) and
the bucketed executable cache (engine.py) all operate on this protocol, so
registering a new workload here is the ONLY step needed to route it through
the sample-free pipeline end to end (DESIGN.md §3).

The registered workloads:

  * :class:`GemmWorkload`        — C[M,N] = A[M,K] @ B[K,N], dynamic M,
  * :class:`GroupedGemmWorkload` — ragged batched GEMM over a shared expert
    weight stack (MoE FFN), dynamic capacity with PER-GROUP runtime extents,
  * :class:`AttentionWorkload`   — flash attention, dynamic sequence length
    (both GEMMs of attention share the seq-tiled lattice: the l1 m-tile is
    the query block, the l1 k-tile the key/value block),
  * :class:`DecodeAttentionWorkload` — single-token decode against a
    kv-bucketed cache (shares the attention lattice),
  * :class:`Conv2dWorkload`      — Conv2D through the im2col GEMM view,
    dynamic batch/spatial (M = b*h'*w').
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Mapping

import numpy as np

from repro.core.hardware import HardwareSpec
from repro.core.rkernel import (
    AnalyzeType,
    LayerMetaInfo,
    LoopType,
    RKernelProgram,
)

__all__ = [
    "Workload",
    "GemmWorkload",
    "GroupedGemmWorkload",
    "AttentionWorkload",
    "DecodeAttentionWorkload",
    "Conv2dWorkload",
    "SelectionDeviationError",
    "WORKLOADS",
    "register_workload",
    "make_workload",
]

Tile = tuple[int, int, int]

# kind -> workload class; the single registry the engine serves from.
WORKLOADS: dict[str, type["Workload"]] = {}


def register_workload(cls: type["Workload"]) -> type["Workload"]:
    """Class decorator: expose a workload to the engine by its ``kind``."""
    if not cls.kind:
        raise ValueError(f"{cls.__name__} must set a non-empty `kind`")
    WORKLOADS[cls.kind] = cls
    return cls


def make_workload(kind: str, **kwargs: Any) -> "Workload":
    try:
        cls = WORKLOADS[kind]
    except KeyError:
        raise KeyError(
            f"unknown workload {kind!r}; registered: {sorted(WORKLOADS)}"
        ) from None
    return cls(**kwargs)


def _make_program(
    hw: HardwareSpec, kind: str, funcs: Mapping[int, tuple[str, str, str]]
) -> RKernelProgram:
    """Shared rKernel skeleton (paper Fig. 10): PL loops at the top level,
    TSL below, TRL on k everywhere; empirical analyzer only at level 0."""
    layers = []
    for depth in range(hw.num_levels):
        load, store, compute = funcs.get(depth, ("", "", ""))
        layers.append(
            LayerMetaInfo(
                layer_depth=depth,
                loop_type={
                    "m": LoopType.PARALLEL if depth == hw.num_levels - 1
                    else LoopType.TEMPORAL_SPATIAL,
                    "n": LoopType.PARALLEL if depth == hw.num_levels - 1
                    else LoopType.TEMPORAL_SPATIAL,
                    "k": LoopType.TEMPORAL_REDUCTION,
                },
                analyzer=AnalyzeType.EMPIRICAL if depth == 0
                else AnalyzeType.ANALYTICAL,
                load_func=load,
                store_func=store,
                compute_func=compute,
            )
        )
    return RKernelProgram(kind=kind, layers=tuple(layers), hardware=hw.name)


class SelectionDeviationError(RuntimeError):
    """An executable would have to deviate from its Selection to run.

    The masked-tail kernels honor the selected layer-1 tile verbatim (tails
    are masked in-kernel, never clamped), so the only way a Selection can
    fail to be honored is an internal inconsistency — e.g. a bucket that is
    not a multiple of its own tile.  Raising beats silently running a tile
    the cost model never priced.
    """


def _check_bucket_tiles(kind: str, sel, pairs) -> None:
    """Every (bucket extent, tile) pair must divide exactly — the staged
    buffers are bucket-shaped, so a non-dividing tile would force the grid
    to deviate from the priced launch geometry."""
    for name, extent, tile in pairs:
        if tile < 1 or extent % tile:
            raise SelectionDeviationError(
                f"{kind}: bucket {name}={extent} is not a multiple of the "
                f"selected l1 tile {tile} (strategy l1={sel.strategy.l1}, "
                f"bucket={sel.bucket}); refusing to clamp the tile"
            )


@dataclasses.dataclass(frozen=True)
class Workload:
    """Protocol base.  A workload is viewed through its (m, n, k) contraction:
    ``m`` is the (single) dynamic extent; ``n``/``k`` may be static (GEMM,
    conv) or tied to the dynamic extent (attention's key length).

    Subclasses override the hooks below; the defaults encode the plain-GEMM
    behaviour so GEMM-like workloads (conv) stay thin.
    """

    kind: ClassVar[str] = ""
    axis_names: ClassVar[tuple[str, ...]] = ("m", "n", "k")
    # Which tile axes scale with the dynamic extent at runtime.  The selector
    # uses this to enumerate grid breakpoints sample-free (buckets_upto).
    dynamic_tile_axes: ClassVar[tuple[int, ...]] = (0,)

    # ---- call-site binding (registry-driven ops) --------------------------
    # These two classmethods are what makes ``repro.vortex.ops.<kind>``
    # work with no engine edits: the engine resolves a call site entirely
    # through the registry — ``dispatch_key`` gives the raw-tuple hot-path
    # key (ints/flags straight off the arrays, no dataclass construction),
    # ``bind`` constructs the Workload instance on the first call per key.

    @classmethod
    def bind(cls, *args: Any, **kwargs: Any) -> "Workload":
        """Construct the workload instance implied by a call site: runtime
        arrays in ``args`` (what the executable consumes), workload
        parameters in ``kwargs`` (masking flags, strides, ...)."""
        raise NotImplementedError(
            f"{cls.__name__} does not define bind(); it cannot be called "
            "through vortex.ops — use vortex.compile(workload) with an "
            "explicit instance instead"
        )

    @classmethod
    def dispatch_key(cls, *args: Any, **kwargs: Any) -> tuple | None:
        """Cheap hashable key identifying the call-site signature (the
        static dims/flags, NOT the dynamic extent).  Returning None opts
        out of the raw-tuple dispatch cache: every call pays bind()."""
        return None

    # ---- identity --------------------------------------------------------

    @property
    def signature(self) -> tuple:
        """Engine-level cache key: one compiled VortexKernel per signature."""
        return (self.kind,) + tuple(
            getattr(self, f.name) for f in dataclasses.fields(self)
        )

    @property
    def lattice_key(self) -> tuple:
        """Scored-lattice cache key: the subset of the signature that the
        candidate generator + analyzer actually depend on.  Workloads whose
        runtime flags (masking etc.) don't change tile costs share scores."""
        return self.signature

    # ---- contraction view ------------------------------------------------

    def runtime_dims(self, m_runtime: int | None = None) -> Tile:
        """Map the dynamic extent to concrete (M, N, K)."""
        raise NotImplementedError

    def flops(self, m: int | None = None) -> float:
        M, N, K = self.runtime_dims(m)
        return 2.0 * M * N * K

    # ---- capacity models (InitCands hardware limits) ---------------------

    def l0_fragment_bytes(self, tile: Tile) -> int:
        """Register-file bytes of one level-0 operand fragment."""
        m, n, k = tile
        return (m * k + k * n) * self.dtype_bytes + m * n * self.acc_bytes

    def l1_tile_bytes(self, tile: Tile) -> int:
        """VMEM bytes the Pallas kernel allocates for one layer-1 tile: the
        pipeline double-buffers every block (two A, two B and two output
        blocks), plus the resident f32 accumulator scratch.  Candidate
        generation holds this under the level-1 capacity, which is also
        the ``vmem_limit_bytes`` the kernel hands the compiler."""
        m, n, k = tile
        blocks = (m * k + k * n + m * n) * self.dtype_bytes
        return 2 * blocks + m * n * self.acc_bytes

    def l0_axis_multipliers(self) -> Tile:
        """Upper pow2 multipliers over the native tile for level-0 ranges."""
        return (16, 4, 4)

    def l1_axis_caps(self, native: Tile) -> Tile:
        """Absolute upper bounds for the level-1 pow2 ranges."""
        return (8192, 8192, 8192)

    # ---- Eq. 2 grid-level traffic (scalar or numpy arrays) ---------------

    def tile_traffic_bytes(self, m1, n1, k1) -> tuple:
        """(load, store) HBM bytes per layer-1 tile per reduction step."""
        load = (m1 * k1 + k1 * n1) * self.dtype_bytes
        store = m1 * n1 * self.dtype_bytes
        return load, store

    # ---- runtime geometry -------------------------------------------------

    def bucket_dims(self, grid: Tile, l1: Tile) -> Tile:
        """Executable-cache key shape.  Padding is confined to the dynamic
        dims and only up to the lattice tile; static dims appear at their
        TRUE size (the executable pads them internally if its blocks need
        it) — the sample-free bucketing contract (DESIGN.md §4)."""
        _, N, K = self.runtime_dims(1)
        return (grid[0] * l1[0], N, K)

    def dynamic_bucket(self, sel) -> int:
        """The padded DYNAMIC extent of a Selection — what serving layers
        quantize to (``CompiledOp.bucket``).  The default is the padded m
        axis; workloads whose dynamic dim lives elsewhere in the
        contraction view (decode attention: the kv/reduction axis)
        override this to point at the right bucket component."""
        return sel.padded_m

    # ---- rKernel program --------------------------------------------------

    def program(self, hw: HardwareSpec) -> RKernelProgram:
        raise NotImplementedError

    # ---- execution (engine hooks): the masked-tail staging contract -------
    # ``sel`` below is a selector.Selection; jax is imported lazily so the
    # analytical core stays importable without an accelerator stack.
    #
    # The fused per-bucket executable built by ``build_executable`` consumes
    # bucket-shaped buffers PLUS the true runtime extents as trailing i32
    # scalars (``runtime_scalars``), and masks the pad tail in-kernel — the
    # pad region of a staged buffer may hold ARBITRARY GARBAGE (stale bytes
    # from an earlier call), never relying on zero fill.  The engine:
    #
    #   1. maps the call args through ``stage_view`` (identity for GEMM and
    #      attention; im2col for conv),
    #   2. compares each view arg's shape against ``staged_shapes`` — args
    #      that already match run with ZERO copies (the aligned fast path),
    #   3. stages mismatched args into engine-owned, donated bucket buffers
    #      (``lax.dynamic_update_slice``: O(true-size) writes, no alloc, no
    #      zero-fill) and launches the one compiled program,
    #   4. slices the bucket-shaped output back via ``finalize``.
    #
    # ``prepare`` (zero-pad the view to the bucket) remains as the REFERENCE
    # path: functionally identical, used for parity tests and for calls that
    # arrive as tracers inside an enclosing jit (where XLA fuses the pads
    # into the surrounding program anyway and engine-owned buffers must not
    # be captured).

    supports_staging: ClassVar[bool] = False
    # Whether finalize() performs a boundary copy (the out[:m] slice) on
    # unaligned calls.  Workloads whose output shape never depends on the
    # bucket (decode attention: out is always (b, h, 1, d)) set this False
    # so DispatchStats.unstage_copies stays an honest copy count.
    unstages: ClassVar[bool] = True
    def dynamic_extent(self, *args) -> int:
        """The runtime value of the dynamic dim, from the call arguments."""
        raise NotImplementedError

    def exec_key(self, *args) -> tuple:
        """Extra executable-cache key parts beyond the bucket (outer dims
        that the compiled artifact is specialized on)."""
        return ()

    def stage_view(self, *args) -> tuple:
        """Map call args to the arrays the fused executable consumes
        (identity unless the workload transforms data first, e.g. im2col)."""
        return args

    def staged_shapes(self, sel, *view) -> tuple:
        """Per view arg: the bucket-shaped staging-buffer shape, or None
        for static args that are passed through unstaged."""
        raise NotImplementedError

    def runtime_scalars(self, sel, *view) -> tuple:
        """True runtime extents appended to every executable call as i32
        scalars — what the masked-tail kernels mask against."""
        return ()

    def prepare(self, sel, *view) -> tuple:
        """Reference path: zero-pad the view args to the bucket shapes."""
        raise NotImplementedError

    def finalize(self, sel, out, *args):
        """Slice the bucket-shaped output back to the true extents (and
        reshape where the view changed layout).  Must be an identity-cheap
        no-op when the call was already bucket-aligned."""
        raise NotImplementedError

    def build_executable(
        self, sel, *, impl: str, vmem_limit_bytes: int | None = None
    ) -> Callable:
        """Build the fused bucket-shaped executable for a runtime selection:
        ``fn(*bucket_view_args, *runtime_scalars) -> bucket-shaped out``.
        ``vmem_limit_bytes`` is what a Pallas kernel may claim from the
        compiler (the hardware's level-1 capacity).  Raises
        :class:`SelectionDeviationError` rather than adjusting the selected
        tile."""
        raise NotImplementedError

    def example_args(self, sel, *args) -> tuple:
        """Zero arrays + scalars matching the executable's full signature
        (AOT lowering / warmup)."""
        raise NotImplementedError

    def reference(self, *args):
        """Flat (non-hierarchized) JAX reference for correctness tests."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------


@register_workload
@dataclasses.dataclass(frozen=True)
class GemmWorkload(Workload):
    """A (possibly dynamic) GEMM: C[M, N] = A[M, K] @ B[K, N].

    ``dynamic_dims`` lists the dims unknown until runtime (for LM inference
    that is M = batch*seq; N and K are weights-side and static).
    """

    M: int | None
    N: int
    K: int
    dtype_bytes: int = 2
    acc_bytes: int = 4
    dynamic_dims: tuple[str, ...] = ("M",)

    kind: ClassVar[str] = "gemm"
    supports_staging: ClassVar[bool] = True

    @classmethod
    def bind(cls, a, b) -> "GemmWorkload":
        return cls(M=None, N=b.shape[1], K=b.shape[0])

    @classmethod
    def dispatch_key(cls, a, b) -> tuple:
        return (b.shape[0], b.shape[1])

    def runtime_dims(self, m_runtime: int | None = None) -> Tile:
        m = self.M if m_runtime is None else m_runtime
        assert m is not None, "runtime M required for dynamic workloads"
        return (m, self.N, self.K)

    def flops(self, m: int | None = None) -> float:
        m = self.M if m is None else m
        assert m is not None
        return 2.0 * m * self.N * self.K

    def program(self, hw: HardwareSpec) -> RKernelProgram:
        return _make_program(
            hw,
            self.kind,
            {
                0: ("load_tile_to_reg", "store_reg", "dot"),
                1: ("copy_hbm_to_vmem", "copy_vmem_to_hbm", ""),
            },
        )

    # -- execution ---------------------------------------------------------

    def dynamic_extent(self, a, b) -> int:
        return a.shape[0]

    def staged_shapes(self, sel, a, b) -> tuple:
        return ((sel.padded_m, self.K), None)

    def runtime_scalars(self, sel, a, b) -> tuple:
        return (np.int32(a.shape[0]),)

    def prepare(self, sel, a, b) -> tuple:
        import jax.numpy as jnp

        mp = sel.padded_m
        if mp != a.shape[0]:
            a = jnp.pad(a, ((0, mp - a.shape[0]), (0, 0)))
        return a, b

    def finalize(self, sel, out, a, b):
        m = a.shape[0]
        return out[:m] if sel.padded_m != m else out

    def build_executable(
        self, sel, *, impl: str, vmem_limit_bytes: int | None = None
    ):
        import jax
        import jax.numpy as jnp

        m1, n1, k1 = sel.strategy.l1
        _check_bucket_tiles(self.kind, sel, (("m", sel.padded_m, m1),))
        if impl == "pallas":
            from repro.kernels.gemm import vortex_gemm

            # The selected tile runs verbatim: N/K tails that don't divide
            # (n1, k1) are masked in-kernel, the m pad tail is masked via
            # the runtime extent — no in-program pads or slices remain.
            def fn(a, b, m_true):
                return vortex_gemm(
                    a, b, m_true, block_m=m1, block_n=n1, block_k=k1,
                    vmem_limit_bytes=vmem_limit_bytes,
                )

        else:

            def fn(a, b, m_true):
                # Rows of A @ B are independent, so garbage pad rows cannot
                # contaminate the real rows; the extent scalar is unused.
                del m_true
                return jax.lax.dot_general(
                    a, b, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(a.dtype)

        return fn

    def example_args(self, sel, *args) -> tuple:
        import jax.numpy as jnp

        # Match the caller's dtypes when representative args are present:
        # the AOT artifact lowered from these IS the steady-state fast
        # path, and a dtype mismatch would demote every call to jit
        # dispatch.
        da = args[0].dtype if args else jnp.float32
        db = args[1].dtype if args else jnp.float32
        return (
            jnp.zeros((sel.padded_m, self.K), da),
            jnp.zeros((self.K, self.N), db),
            np.int32(sel.padded_m),
        )

    def reference(self, a, b):
        from repro.kernels.ref import ref_gemm

        return ref_gemm(a, b)


# ---------------------------------------------------------------------------
# Grouped GEMM (ragged MoE expert FFN)
# ---------------------------------------------------------------------------


@register_workload
@dataclasses.dataclass(frozen=True)
class GroupedGemmWorkload(Workload):
    """Ragged grouped GEMM: out[g] = x[g] @ w[g // (G//E)], per-group extents.

    The MoE expert FFN after capacity-bucketed routing: G groups of
    capacity-shaped ``(C, K)`` activation slabs multiply against a shared
    ``(E, K, N)`` expert weight stack (``r = G // E`` consecutive groups —
    expert-major layout — share each stack entry).  Only ``counts[g]`` rows
    of slab g are real; the rest is routing pad.

    This is the first workload whose DYNAMIC extent is a *routing outcome*
    rather than an input length: the capacity C moves with how the router
    distributed the batch's tokens, which is exactly the dynamism
    sample-driven tuners cannot pre-enumerate.  The masked-tail contract
    handles it unchanged — C buckets like any dynamic extent, and the true
    extents ride into the kernel as a ``(G,)`` i32 vector (the per-row
    ``kv_len`` contract of batched decode, lifted to per-group row counts).
    One launch covers all G groups at any routing skew.

    Selection prices the PER-GROUP ``(C, N, K)`` contraction view: G is a
    constant multiplier on every candidate's time under Eq. 2-4, so the
    per-group argmin is the whole-launch argmin and the plain-GEMM lattice
    applies verbatim (``lattice_key`` shares the scored gemm lattice, like
    decode shares prefill attention's).  ``flops()`` still reports the TRUE
    G-scaled work.

    Call signature: ``grouped_gemm(x, w, counts)`` with x ``(G, C, K)``,
    w ``(E, K, N)``, counts ``(G,)`` i32.  Rows of ``x[g]`` at or past
    ``counts[g]`` may hold arbitrary garbage (stale staging bytes, NaNs);
    the matching output rows are exactly zero in every impl, which keeps
    staged dispatch bit-identical to the zero-padded reference path.
    """

    C: int | None  # capacity (rows per group), dynamic
    G: int  # total groups = E * groups_per_expert
    E: int  # weight stack entries
    N: int
    K: int
    dtype_bytes: int = 2
    acc_bytes: int = 4
    dynamic_dims: tuple[str, ...] = ("C",)

    kind: ClassVar[str] = "grouped_gemm"
    supports_staging: ClassVar[bool] = True

    @classmethod
    def bind(cls, x, w, counts) -> "GroupedGemmWorkload":
        return cls(
            C=None, G=x.shape[0], E=w.shape[0], N=w.shape[2], K=w.shape[1]
        )

    @classmethod
    def dispatch_key(cls, x, w, counts) -> tuple:
        return (x.shape[0], w.shape[0], w.shape[1], w.shape[2])

    @property
    def lattice_key(self) -> tuple:
        # The per-group (C, N, K) view prices exactly like a plain GEMM of
        # the same (N, K) — identical capacity/traffic models, and G is a
        # constant factor across candidates so the ranking is unchanged.
        # Share the scored gemm lattice (the literal GemmWorkload signature,
        # so both workloads hash to one cache entry).
        return (
            "gemm", None, self.N, self.K,
            self.dtype_bytes, self.acc_bytes, ("M",),
        )

    def runtime_dims(self, m_runtime: int | None = None) -> Tile:
        c = self.C if m_runtime is None else m_runtime
        assert c is not None, "runtime capacity required"
        return (c, self.N, self.K)

    def flops(self, m: int | None = None) -> float:
        c = self.C if m is None else m
        assert c is not None
        return 2.0 * self.G * c * self.N * self.K  # true work, all groups

    def program(self, hw: HardwareSpec) -> RKernelProgram:
        return _make_program(
            hw,
            self.kind,
            {
                0: ("load_tile_to_reg", "store_reg", "dot"),
                1: ("copy_hbm_to_vmem", "copy_vmem_to_hbm", ""),
            },
        )

    # -- execution ---------------------------------------------------------

    def dynamic_extent(self, x, w, counts) -> int:
        return x.shape[1]

    def stage_view(self, x, w, counts) -> tuple:
        # Coerce list/tuple/int-dtype counts to a concrete (G,) i32 array so
        # the steady-state call matches the AOT artifact's dtypes; traced
        # and already-i32 values pass through.
        if isinstance(counts, (list, tuple)) or (
            getattr(counts, "dtype", None) != np.int32
            and not hasattr(counts, "aval")
        ):
            counts = np.asarray(counts, np.int32).reshape(self.G)
        return x, w, counts

    def staged_shapes(self, sel, x, w, counts) -> tuple:
        # Only the activation slabs are bucket-shaped (on the capacity
        # axis); weights and the counts vector pass through unstaged.
        return ((self.G, sel.padded_m, self.K), None, None)

    def runtime_scalars(self, sel, x, w, counts) -> tuple:
        return ()  # the per-group extents already ride in the view

    def prepare(self, sel, x, w, counts) -> tuple:
        import jax.numpy as jnp

        cp = sel.padded_m
        if cp != x.shape[1]:
            x = jnp.pad(x, ((0, 0), (0, cp - x.shape[1]), (0, 0)))
        return x, w, counts

    def finalize(self, sel, out, x, w, counts):
        c = x.shape[1]
        return out[:, :c] if sel.padded_m != c else out

    def build_executable(
        self, sel, *, impl: str, vmem_limit_bytes: int | None = None
    ):
        import jax.numpy as jnp

        m1, n1, k1 = sel.strategy.l1
        _check_bucket_tiles(self.kind, sel, (("c", sel.padded_m, m1),))
        G, E, K = self.G, self.E, self.K

        if impl == "pallas":
            from repro.kernels.grouped_gemm import vortex_grouped_gemm

            def fn(x, w, counts):
                return vortex_grouped_gemm(
                    x, w, counts, block_m=m1, block_n=n1, block_k=k1,
                    vmem_limit_bytes=vmem_limit_bytes,
                )

        else:

            def fn(x, w, counts):
                # Mask rows at each group's extent BEFORE the matmul: the
                # staged pad tail is garbage, and rows past counts[g] must
                # come out exactly zero (the kernel contract).  The einsum
                # over the (E, r, C, K) reshape shares the weight stack
                # without materializing a per-group copy.
                cb = x.shape[1]
                valid = (
                    jnp.arange(cb)[None, :]
                    < jnp.asarray(counts, jnp.int32).reshape(G, 1)
                )
                xf = jnp.where(valid[..., None], x.astype(jnp.float32), 0)
                out = jnp.einsum(
                    "erck,ekn->ercn",
                    xf.reshape(E, G // E, cb, K),
                    w.astype(jnp.float32),
                )
                return out.reshape(G, cb, -1).astype(x.dtype)

        return fn

    def example_args(self, sel, *args) -> tuple:
        import jax.numpy as jnp

        dx = args[0].dtype if args else jnp.float32
        dw = args[1].dtype if args else jnp.float32
        return (
            jnp.zeros((self.G, sel.padded_m, self.K), dx),
            jnp.zeros((self.E, self.K, self.N), dw),
            np.zeros((self.G,), np.int32),
        )

    def reference(self, x, w, counts):
        from repro.kernels.ref import ref_grouped_gemm

        return ref_grouped_gemm(x, w, counts)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


@register_workload
@dataclasses.dataclass(frozen=True)
class AttentionWorkload(Workload):
    """Flash attention with a dynamic sequence length.

    Both contractions (QK^T: (sq,d)@(d,skv); PV: (sq,skv)@(skv,d)) tile on
    the SAME sequence blocks, so one lattice governs both: the l1 m-tile is
    the query block and the l1 k-tile the key/value block (the pairing the
    Pallas kernel consumes as (block_q, block_k)).  The n axis is pinned to
    the native lane tile — head_dim is static and fits one block — which
    keeps the attention lattice free of meaningless n variation.

    Padding correctness comes from an EXPLICIT key-validity mask: the true
    kv length rides along as a runtime scalar and the kernel masks scores
    (and zeroes value rows) past it, so bucket pad — even garbage bytes in
    a staging buffer — can never reach a real query row.  The causal
    structure is no longer load-bearing for padding, which is why
    ``causal=False`` (encoder/bidirectional attention) buckets just as
    safely as the causal LM case.
    """

    seq: int | None
    head_dim: int
    causal: bool = True
    window: int | None = None
    softcap: float | None = None
    dtype_bytes: int = 2
    acc_bytes: int = 4
    dynamic_dims: tuple[str, ...] = ("seq",)

    kind: ClassVar[str] = "attention"
    dynamic_tile_axes: ClassVar[tuple[int, ...]] = (0, 2)
    supports_staging: ClassVar[bool] = True

    @classmethod
    def bind(
        cls, q, k, v, *, causal: bool = True,
        window: int | None = None, softcap: float | None = None,
    ) -> "AttentionWorkload":
        return cls(
            seq=None, head_dim=q.shape[-1], causal=causal,
            window=window, softcap=softcap,
        )

    @classmethod
    def dispatch_key(
        cls, q, k, v, *, causal: bool = True,
        window: int | None = None, softcap: float | None = None,
    ) -> tuple:
        return (q.shape[-1], causal, window, softcap)

    @property
    def lattice_key(self) -> tuple:
        # Masking flags don't move tile costs; share scored lattices.
        return (self.kind, self.head_dim, self.dtype_bytes, self.acc_bytes)

    def runtime_dims(self, m_runtime: int | None = None) -> Tile:
        s = self.seq if m_runtime is None else m_runtime
        assert s is not None, "runtime seq required"
        return (s, self.head_dim, s)

    def flops(self, m: int | None = None) -> float:
        s = self.seq if m is None else m
        assert s is not None
        return 4.0 * s * s * self.head_dim  # QK^T + PV

    def l1_tile_bytes(self, tile: Tile) -> int:
        m1, _, k1 = tile
        d = self.head_dim
        # Two buffers each of the q, k, v and output blocks.
        blocks = 2 * (2 * m1 * d + 2 * k1 * d) * self.dtype_bytes
        # acc + running max/sum scratch, and the f32 score block.
        resident = m1 * d * self.acc_bytes + 2 * m1 * 4 + m1 * k1 * 4
        return blocks + resident

    def l0_axis_multipliers(self) -> Tile:
        return (16, 1, 4)  # n pinned to the native lane tile

    def l1_axis_caps(self, native: Tile) -> Tile:
        return (8192, native[1], 8192)

    def tile_traffic_bytes(self, m1, n1, k1) -> tuple:
        d = self.head_dim
        load = 2 * k1 * d * self.dtype_bytes  # stream K and V blocks
        store = m1 * d * self.dtype_bytes  # output block, once per tile
        return load, store

    def bucket_dims(self, grid: Tile, l1: Tile) -> Tile:
        return (grid[0] * l1[0], self.head_dim, grid[2] * l1[2])

    def program(self, hw: HardwareSpec) -> RKernelProgram:
        return _make_program(
            hw,
            self.kind,
            {
                0: ("load_tile_to_reg", "store_reg", "dot"),
                1: ("copy_qkv_to_vmem", "online_softmax_store", ""),
            },
        )

    # -- execution ---------------------------------------------------------

    def dynamic_extent(self, q, k, v) -> int:
        assert q.shape[-2] == k.shape[-2], (
            "engine attention is self-attention: query/key lengths must "
            f"match, got {q.shape[-2]} vs {k.shape[-2]}"
        )
        return q.shape[-2]

    def exec_key(self, q, k, v) -> tuple:
        # Outer (batch, heads) dims specialize the compiled artifact.
        return (q.shape[0], q.shape[1], k.shape[1])

    def staged_shapes(self, sel, q, k, v) -> tuple:
        pq, d, pkv = sel.bucket
        b, hq, _, _ = q.shape
        hkv = k.shape[1]
        return (
            (b, hq, pq, d),
            (b, hkv, pkv, d),
            (b, hkv, pkv, d),
        )

    def runtime_scalars(self, sel, q, k, v) -> tuple:
        return (np.int32(k.shape[-2]),)

    def prepare(self, sel, q, k, v) -> tuple:
        import jax.numpy as jnp

        pq, _, pkv = sel.bucket
        sq = q.shape[-2]
        if pq != sq:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pq - sq), (0, 0)))
        if pkv != k.shape[-2]:
            pad = ((0, 0), (0, 0), (0, pkv - k.shape[-2]), (0, 0))
            k = jnp.pad(k, pad)
            v = jnp.pad(v, pad)
        return q, k, v

    def finalize(self, sel, out, q, k, v):
        sq = q.shape[-2]
        return out[..., :sq, :] if sel.bucket[0] != sq else out

    def build_executable(
        self, sel, *, impl: str, vmem_limit_bytes: int | None = None
    ):
        pq, _, pkv = sel.bucket
        m1, _, k1 = sel.strategy.l1
        _check_bucket_tiles(
            self.kind, sel, (("q", pq, m1), ("kv", pkv, k1))
        )
        causal, window, softcap = self.causal, self.window, self.softcap

        if impl == "pallas":
            from repro.kernels.attention import flash_attention

            def fn(q, k, v, kv_len):
                return flash_attention(
                    q, k, v, kv_len, block_q=m1, block_k=k1,
                    causal=causal, window=window, softcap=softcap,
                    vmem_limit_bytes=vmem_limit_bytes,
                )

        else:
            from repro.kernels.ref import chunked_attention

            def fn(q, k, v, kv_len):
                return chunked_attention(
                    q, k, v, causal=causal, window=window, softcap=softcap,
                    chunk=k1, kv_len=kv_len,
                )

        return fn

    def example_args(self, sel, *args) -> tuple:
        import jax.numpy as jnp

        pq, d, pkv = sel.bucket
        if args:
            b, hq, hkv = self.exec_key(*args)
            dts = tuple(a.dtype for a in args)
        else:
            b, hq, hkv = 1, 1, 1
            dts = (jnp.float32,) * 3
        return (
            jnp.zeros((b, hq, pq, d), dts[0]),
            jnp.zeros((b, hkv, pkv, d), dts[1]),
            jnp.zeros((b, hkv, pkv, d), dts[2]),
            np.int32(pkv),
        )

    def reference(self, q, k, v):
        from repro.kernels.ref import ref_attention

        return ref_attention(
            q, k, v, causal=self.causal, window=self.window,
            softcap=self.softcap,
        )


# ---------------------------------------------------------------------------
# Decode attention (q_len == 1 against a kv-bucketed cache)
# ---------------------------------------------------------------------------


@register_workload
@dataclasses.dataclass(frozen=True)
class DecodeAttentionWorkload(AttentionWorkload):
    """Single-token decode attention against a KV cache.

    The DYNAMIC extent is the cache length S — a static per-call-site
    shape, which is what makes selection work both eagerly and inside a
    traced decode program.  Selection prices the same (S, head_dim, S)
    view as prefill :class:`AttentionWorkload`: decode streams exactly the
    kv block (l1 k-tile) the prefill kernel would stream at sequence
    length S, so the decode kv-bucket set IS the prefill kv-bucket set
    (lattice-granular, not degenerate — a literal (1, d, S) view makes
    Eq. 2-4 flat in the k-tile and the argmin collapses to the smallest
    tile, a bucket every 2 tokens).  Only the q side differs at
    execution: q_len == 1 is static, so the lattice m-tile never
    materializes and the kernel takes its decode grid (one step per
    batch row and kv block, every KV head in the block).  The TRUE
    number of valid cache rows rides as the ``kv_len`` runtime scalar
    (a Python int in eager serving, a traced i32 inside a compiled
    decode step): scores past it are masked and value rows zeroed by the
    kernel, so the cache tail beyond ``kv_len`` — bucket pad, stale
    staging bytes, NaNs — can never reach the query row.  Causality needs no flag: the query sits at
    absolute position ``kv_len - 1``, so the key-validity mask IS the
    causal mask; sliding windows re-base through the same offset.

    Call signature: ``decode_attention(q, k, v, kv_len)`` with q
    (b, hq, 1, d) and k/v (b, hkv, S, d), S >= kv_len >= 1.  ``kv_len``
    is a scalar (whole batch at one position) or a (b,) i32 vector giving
    each batch row its OWN valid-row count — mixed-progress batched
    decode, one launch serving rows at different positions, a 0 masking a
    row to zero work.  The two ranks lower to different AOT programs
    (``exec_key`` carries the rank), and per-row causality still needs no
    flag: row i's query sits at ``kv_len[i] - 1``.  Two serving shapes
    hit the padding-free path:

      * S already a kv bucket (the serving cache lives in bucket-shaped
        buffers and grows in place by ``dynamic_update_slice``) — aligned,
        one launch, zero copies, every token;
      * arbitrary S — k/v stage into engine-owned kv-bucket buffers whose
        tails keep stale garbage, then one launch.

    The scored lattice is SHARED with :class:`AttentionWorkload` (same
    ``lattice_key``): the kv block is the same l1 k-tile the prefill
    kernel streams, so decode adds zero offline lattice work.
    """

    kind: ClassVar[str] = "decode_attention"
    supports_staging: ClassVar[bool] = True
    unstages: ClassVar[bool] = False  # out is (b, hq, 1, d): nothing to slice

    @classmethod
    def bind(
        cls, q, k, v, kv_len, *,
        window: int | None = None, softcap: float | None = None,
    ) -> "DecodeAttentionWorkload":
        return cls(
            seq=None, head_dim=q.shape[-1], causal=True,
            window=window, softcap=softcap,
        )

    @classmethod
    def dispatch_key(
        cls, q, k, v, kv_len, *,
        window: int | None = None, softcap: float | None = None,
    ) -> tuple:
        return (q.shape[-1], window, softcap)

    @property
    def lattice_key(self) -> tuple:
        # Decode streams the same (block_q, block_k) tile space as prefill
        # attention; share its scored lattices (the literal kind string —
        # NOT self.kind — so both workloads hash to one cache entry).
        return ("attention", self.head_dim, self.dtype_bytes, self.acc_bytes)

    # runtime_dims stays the inherited (S, head_dim, S) prefill view — the
    # selection pricing contract above.  flops() reports the TRUE decode
    # work (one query row), not the priced view.

    def flops(self, m: int | None = None) -> float:
        s = self.seq if m is None else m
        assert s is not None
        return 4.0 * s * self.head_dim  # one query row: QK^T + PV

    def bucket_dims(self, grid: Tile, l1: Tile) -> Tile:
        return (1, self.head_dim, grid[2] * l1[2])

    def dynamic_bucket(self, sel) -> int:
        return sel.bucket[2]

    # -- execution ---------------------------------------------------------

    def dynamic_extent(self, q, k, v, kv_len) -> int:
        assert q.shape[-2] == 1, (
            f"decode attention takes ONE query row, got q_len={q.shape[-2]}"
        )
        return k.shape[-2]

    def exec_key(self, q, k, v, kv_len) -> tuple:
        # kv_len's rank is part of the key: a scalar (whole batch at one
        # position) and a (b,) per-row vector (mixed-progress batched
        # decode) lower to DIFFERENT programs — the AOT artifact is
        # shape-specialized, so they must not share a cache entry.
        return (
            q.shape[0], q.shape[1], k.shape[1],
            getattr(kv_len, "ndim", 0),
        )

    def stage_view(self, q, k, v, kv_len) -> tuple:
        # Coerce a Python-int kv_len to np.int32 so the steady-state call
        # matches the AOT artifact's dtypes (a bare int would demote every
        # dispatch to jit re-dispatch); traced/jax values (including (b,)
        # per-row vectors) pass through.
        if isinstance(kv_len, (bool, int, np.integer)):
            kv_len = np.int32(kv_len)
        return q, k, v, kv_len

    def staged_shapes(self, sel, q, k, v, kv_len) -> tuple:
        _, d, pkv = sel.bucket
        b, hkv = k.shape[0], k.shape[1]
        # q and the kv_len scalar pass through unstaged; only the cache
        # buffers are bucket-shaped.
        return (None, (b, hkv, pkv, d), (b, hkv, pkv, d), None)

    def runtime_scalars(self, sel, q, k, v, kv_len) -> tuple:
        return ()  # kv_len already rides in the view

    def prepare(self, sel, q, k, v, kv_len) -> tuple:
        import jax.numpy as jnp

        pkv = sel.bucket[2]
        if pkv != k.shape[-2]:
            pad = ((0, 0), (0, 0), (0, pkv - k.shape[-2]), (0, 0))
            k = jnp.pad(k, pad)
            v = jnp.pad(v, pad)
        return q, k, v, kv_len

    def finalize(self, sel, out, q, k, v, kv_len):
        return out  # (b, hq, 1, d) — never bucket-shaped

    def build_executable(
        self, sel, *, impl: str, vmem_limit_bytes: int | None = None
    ):
        pkv = sel.bucket[2]
        _, _, k1 = sel.strategy.l1
        _check_bucket_tiles(self.kind, sel, (("kv", pkv, k1),))
        window, softcap = self.window, self.softcap

        if impl == "pallas":
            from repro.kernels.attention import flash_attention

            def fn(q, k, v, kv_len):
                # causal=False: the kv_len validity mask already excludes
                # every key past the query's absolute position kv_len-1.
                return flash_attention(
                    q, k, v, kv_len, q_offset=kv_len - 1,
                    block_q=1, block_k=k1, causal=False,
                    window=window, softcap=softcap,
                    vmem_limit_bytes=vmem_limit_bytes,
                )

        else:
            from repro.kernels.ref import chunked_attention

            def fn(q, k, v, kv_len):
                return chunked_attention(
                    q, k, v, causal=False, window=window, softcap=softcap,
                    chunk=k1, offset=kv_len - 1, kv_len=kv_len,
                )

        return fn

    def example_args(self, sel, *args) -> tuple:
        import jax.numpy as jnp

        _, d, pkv = sel.bucket
        if args:
            b, hq, hkv, kv_ndim = self.exec_key(*args)
            dts = tuple(a.dtype for a in args[:3])
        else:
            b, hq, hkv, kv_ndim = 1, 1, 1, 0
            dts = (jnp.float32,) * 3
        # The warm kv_len must match the live calls' rank: the AOT program
        # a (b,) vector lowers embeds per-row masking.
        kv_ex = (
            jnp.full((b,), pkv, jnp.int32) if kv_ndim else np.int32(pkv)
        )
        return (
            jnp.zeros((b, hq, 1, d), dts[0]),
            jnp.zeros((b, hkv, pkv, d), dts[1]),
            jnp.zeros((b, hkv, pkv, d), dts[2]),
            kv_ex,
        )

    def reference(self, q, k, v, kv_len):
        from repro.kernels.ref import ref_attention

        if getattr(kv_len, "ndim", 0):
            kv_len = np.asarray(kv_len, np.int32)
        else:
            kv_len = int(kv_len)
        return ref_attention(
            q, k, v, causal=False, window=self.window,
            softcap=self.softcap, offset=kv_len - 1, kv_len=kv_len,
        )


# ---------------------------------------------------------------------------
# Conv2D (im2col GEMM view)
# ---------------------------------------------------------------------------


@register_workload
@dataclasses.dataclass(frozen=True)
class Conv2dWorkload(Workload):
    """Conv2D (VALID padding) lowered to the hierarchized GEMM space.

    im2col turns Conv2D into a GEMM with M = b*h'*w' (dynamic batch and
    spatial extents), N = cout, K = kh*kw*cin — after which the entire
    lattice/analyzer/selector machinery applies unchanged (paper Table 4).
    """

    m: int | None  # b*h'*w', dynamic
    cin: int
    cout: int
    kh: int
    kw: int
    stride: int = 1
    dtype_bytes: int = 2
    acc_bytes: int = 4
    dynamic_dims: tuple[str, ...] = ("m",)

    kind: ClassVar[str] = "conv2d"
    supports_staging: ClassVar[bool] = True

    @classmethod
    def bind(cls, x, w, *, stride: int = 1) -> "Conv2dWorkload":
        kh, kw, cin, cout = w.shape
        return cls(m=None, cin=cin, cout=cout, kh=kh, kw=kw, stride=stride)

    @classmethod
    def dispatch_key(cls, x, w, *, stride: int = 1) -> tuple:
        kh, kw, cin, cout = w.shape
        return (kh, kw, cin, cout, stride)

    @property
    def N(self) -> int:
        return self.cout

    @property
    def K(self) -> int:
        return self.kh * self.kw * self.cin

    def runtime_dims(self, m_runtime: int | None = None) -> Tile:
        m = self.m if m_runtime is None else m_runtime
        assert m is not None, "runtime output-pixel count required"
        return (m, self.N, self.K)

    def program(self, hw: HardwareSpec) -> RKernelProgram:
        return _make_program(
            hw,
            self.kind,
            {
                0: ("load_tile_to_reg", "store_reg", "dot"),
                1: ("im2col_hbm_to_vmem", "copy_vmem_to_hbm", ""),
            },
        )

    # -- execution ---------------------------------------------------------

    def _out_hw(self, x) -> tuple[int, int]:
        _, h, w, _ = x.shape
        return (
            (h - self.kh) // self.stride + 1,
            (w - self.kw) // self.stride + 1,
        )

    def dynamic_extent(self, x, w) -> int:
        ho, wo = self._out_hw(x)
        return x.shape[0] * ho * wo

    def stage_view(self, x, w) -> tuple:
        from repro.kernels.conv import im2col

        cols, _ = im2col(x, self.kh, self.kw, self.stride)
        # conv_general_dilated_patches orders features (cin, kh, kw).
        wmat = w.transpose(2, 0, 1, 3).reshape(self.K, self.cout)
        return cols, wmat

    def staged_shapes(self, sel, cols, wmat) -> tuple:
        return ((sel.padded_m, self.K), None)

    def runtime_scalars(self, sel, cols, wmat) -> tuple:
        return (np.int32(cols.shape[0]),)

    def prepare(self, sel, cols, wmat) -> tuple:
        import jax.numpy as jnp

        m = cols.shape[0]
        if sel.padded_m != m:
            cols = jnp.pad(cols, ((0, sel.padded_m - m), (0, 0)))
        return cols, wmat

    def finalize(self, sel, out, x, w):
        ho, wo = self._out_hw(x)
        m = x.shape[0] * ho * wo
        return out[:m, : self.cout].reshape(x.shape[0], ho, wo, self.cout)

    def build_executable(
        self, sel, *, impl: str, vmem_limit_bytes: int | None = None
    ):
        # The executable is the GEMM-view kernel on the im2col matrix; the
        # im2col expansion itself runs eagerly in stage_view() so the cached
        # artifact depends only on the bucket, not on (b, h, w) directly.
        return GemmWorkload(
            M=None, N=self.N, K=self.K, dtype_bytes=self.dtype_bytes,
            acc_bytes=self.acc_bytes,
        ).build_executable(sel, impl=impl, vmem_limit_bytes=vmem_limit_bytes)

    def example_args(self, sel, *args) -> tuple:
        import jax.numpy as jnp

        # args are the raw (x, w) call args; the executable consumes the
        # im2col view, which keeps the input dtypes.
        dx = args[0].dtype if args else jnp.float32
        dw = args[1].dtype if args else jnp.float32
        return (
            jnp.zeros((sel.padded_m, self.K), dx),
            jnp.zeros((self.K, self.N), dw),
            np.int32(sel.padded_m),
        )

    def reference(self, x, w):
        from repro.kernels.ref import ref_conv2d

        return ref_conv2d(x, w, stride=self.stride, padding="VALID")
