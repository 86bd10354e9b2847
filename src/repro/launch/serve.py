"""Dynamic-shape serving driver — where Vortex earns its keep at runtime.

Requests arrive with arbitrary batch sizes and prompt lengths.  XLA needs
static shapes, so every distinct (batch, prompt_len) would recompile.  The
server quantizes both dims through the vortex engine session it owns:

  * the sequence dim is bucketed by the engine's own selection machinery —
    ``CompiledOp.bucket`` over the model's GEMM signature, i.e. the SAME
    lattice breakpoints the runtime selector bisects (there is no second,
    hand-rolled bucketing scheme in the tree);
  * the request batch dim (an auxiliary outer multiplier) is pow2-bucketed
    (``vortex.pow2_bucket``).

Prefill executables are AOT-compiled per bucket through ONE jit function
(``jit(...).lower(...).compile()``), so ``stats["prefill_compiles"]``
counts real XLA compilations — not per-shape Python wrappers around a jit
that retraces anyway.  Lowering runs under ``engine.use()``: prefill AND
decode attention inside the model dispatch through the engine session, so
the compiled programs embed lattice-selected attention blocks.  (The
engine serves those trace-time calls through its zero-pad reference path
— the pads fuse into the program, and at a bucket-aligned cache length
there is nothing to pad — and counts them as ``traced_calls``; eager
dispatch outside a trace takes the masked-tail staging hot path, whose
launch/copy counters ``engine_dispatch_stats`` surfaces.)

Decode is the third padding-free serving scenario (after aligned and
unaligned prefill): the KV cache lives in kv-BUCKET-shaped buffers (the
decode-attention workload's own bucket set — the same kv buckets prefill
streams), each step runs exactly ONE AOT decode program for the current
(batch-bucket, kv-bucket) pair, and the cache grows IN PLACE by
``dynamic_update_slice`` — the new token's K/V row lands in the bucket
buffer, nothing re-stages per token.  Rows past ``pos`` are dead weight
the kv_len mask never reads.  When ``pos`` outgrows the bucket, the cache
is copied once into the next bucket's buffers (amortized-doubling growth,
so the reachable bucket chain stays logarithmic); ``decode_stats`` (a
DispatchStats) counts launches per token, growth copies and pad
fallbacks (always 0) — surfaced by ``engine_dispatch_stats()`` under
``decode_step``.  ``warmup()`` AOT-compiles the per-bucket prefill AND
decode programs (warming the engine's attention executables through the
session) before traffic arrives.

``prefill="chained"`` swaps the AOT prefill program for the lazy-handle
chain (DESIGN.md §8): the whole model runs eagerly through the engine
session with every dispatch output staying a bucket-shaped
:class:`~repro.core.engine.LazyBucket` that the next dispatch consumes
directly — at a chain-aligned sequence bucket (``chain_seq_bucket``) a
prefill performs ZERO interior unstage+restage pairs, and the decode
cache's k/v leaves consume the attention projections' bucket buffers
without a copy.  The eager per-op reference (``prefill_chained(...,
eager=True)``) runs the identical dispatch sequence on plain arrays and
is bit-identical; the AOT path stays the default and the fallback for
unsupported architectures.

``python -m repro.launch.serve --arch paper-gpt2-124m --smoke --requests 16``
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import math
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AttentionWorkload, DecodeAttentionWorkload, GemmWorkload
from repro.core.engine import DispatchStats
from repro.core.hardware import resolve_platform
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import abstract_cache, decode_updates_in_place
from repro.models.params import init_params
from repro.models.partitioning import make_rules
from repro.models.registry import get_config, get_smoke_config
from repro.runtime import faults
from repro.train.step import make_decode_step, make_prefill_step
from repro.vortex import CompiledOp, Engine, EngineConfig, pow2_bucket

__all__ = [
    "VortexServer",
    "chain_gemm_sigs",
    "Request",
    "KVBucketPool",
    "RequestError",
    "QueueFullError",
    "DeadlineExceeded",
    "CacheOverflowError",
]


def chain_gemm_sigs(cfg) -> list[tuple[int, int]]:
    """Every (K, N) GEMM signature the chained prefill dispatches for
    ``cfg``: q/k/v/o projections, the MLP pair, and the LM head."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    sigs = {
        (d, cfg.n_heads * hd),        # wq
        (d, cfg.n_kv_heads * hd),     # wk / wv
        (cfg.n_heads * hd, d),        # wo
        (d, cfg.vocab_padded),        # lm head
    }
    if any(spec.mlp == "dense" for spec in cfg.pattern):
        sigs.add((d, cfg.d_ff))       # w_in / w_gate
        sigs.add((cfg.d_ff, d))       # w_out
    return sorted(sigs)


class CacheOverflowError(ValueError):
    """The request cannot fit ``max_cache`` even after growth — refused
    up front (before any prefill work) by BOTH admission paths: the
    serial ``generate()`` and the scheduler's ``submit()``.  A
    ``ValueError`` subclass so pre-existing callers matching ValueError
    keep working."""


class QueueFullError(RuntimeError):
    """``submit()`` refused: the scheduler's bounded admission queue
    (``max_queue``) is at capacity — back-pressure, not failure; retry
    after a drain."""


class RequestError(RuntimeError):
    """A typed per-request failure (DESIGN.md §11): the scheduler's
    ``drain()`` RETURNS this (in place of the token array) for a request
    whose admission, cache growth, or decode raised — the step loop
    itself never tears down.  ``stage`` names the failure domain
    (``admit`` / ``grow`` / ``decode`` / ``deadline``)."""

    def __init__(self, request_id: int, stage: str, message: str):
        self.request_id = request_id
        self.stage = stage
        super().__init__(
            f"request {request_id} failed during {stage}: {message}"
        )


class DeadlineExceeded(RequestError):
    """A request's wall-clock ``deadline_s`` expired before completion;
    its rows retire immediately and the slots are reused next step."""

    def __init__(self, request_id: int, deadline_s: float):
        self.deadline_s = deadline_s
        super().__init__(
            request_id, "deadline",
            f"deadline_s={deadline_s} expired before completion",
        )


@dataclasses.dataclass
class Request:
    tokens: np.ndarray  # (batch, prompt_len)
    max_new: int = 8
    # Early-stop token: a row that emits it retires immediately, its
    # remaining output positions filled with the stop token (scheduler
    # path; the serial ``generate()`` path always runs to max_new).
    stop: int | None = None
    # Assigned by the admission queue (launch/scheduler.py) so responses
    # can be matched to submissions; the serial ``generate()`` path never
    # reads it.
    request_id: int | None = None
    # Wall-clock budget from ``submit()`` (scheduler path only): once it
    # expires the request resolves to ``DeadlineExceeded`` instead of
    # occupying slots forever.  None = no deadline.
    deadline_s: float | None = None


class KVBucketPool:
    """Shared pool of kv-bucket cache buffers, leased per request.

    Cache growth used to drop the outgrown bucket's buffers to the GC and
    allocate fresh zero-filled ones; under continuous batching that churn
    happens on every admitted request.  The pool instead PARKS released
    buffers keyed by (shape, dtype) and hands them back on the next lease.
    A reused buffer is returned AS-IS — stale bytes and all — which is
    safe exactly where the masked-tail contract holds: attention k/v
    leaves are only ever read through the kv_len-masked decode workload,
    so rows past each row's extent are never consumed.  Leaves whose
    decode math masks scores but not values (MLA's ckv/k_rope: the
    absorbed PV contraction would hit 0 * garbage) must lease with
    ``zero=True``, which always allocates fresh zeros.

    Every growable cache leaf in flight counts as one active lease
    (``leases_active``; high-water mark ``leases_peak``) whether it came
    from the free list or a fresh allocation — a non-zero ``leases_active``
    at idle is a leak, asserted by the scheduler tests and surfaced via
    ``VortexServer.engine_dispatch_stats()["kv_pool"]``.  ``parked_bytes``
    is the size of the buffers parked now, kept up to date as they are
    parked, leased and dropped; ``retired`` counts the releases that parked
    nothing.  Thread-safe: the admission queue leases/releases from
    submitter and scheduler threads.
    """

    # Parked buffers per (shape, dtype) key; beyond this the oldest are
    # dropped to the GC — the pool bounds memory, it is not a cache of
    # every bucket ever seen.
    _MAX_PARKED = 16

    def __init__(self) -> None:
        self._free: dict[tuple, list[jax.Array]] = {}
        self._lock = threading.Lock()
        self.leases_active = 0
        self.leases_peak = 0
        self.lease_hits = 0
        self.lease_allocs = 0
        self.released = 0
        self.retired = 0
        self.parked_bytes = 0

    def lease(self, shape, dtype, *, zero: bool = False) -> jax.Array:
        """One bucket-shaped buffer: a parked one when available (stale
        contents — callers must read it through a kv_len mask), else a
        fresh zero-filled allocation.  ``zero=True`` always allocates."""
        if faults.ACTIVE is not None:
            faults.ACTIVE.check("pool_lease")
        key = (tuple(shape), jnp.dtype(dtype).name)
        buf = None
        with self._lock:
            free = self._free.get(key) if not zero else None
            while free and buf is None:
                buf = free.pop()
                self.parked_bytes -= buf.nbytes
                if buf.is_deleted():  # donated to a program since parked
                    buf = None
            if buf is not None:
                self.lease_hits += 1
            else:
                self.lease_allocs += 1
            self.leases_active += 1
            self.leases_peak = max(self.leases_peak, self.leases_active)
        if buf is None:
            buf = jnp.zeros(tuple(shape), jnp.dtype(dtype))
        return buf

    def adopt(self, n: int) -> None:
        """Register ``n`` buffers that entered circulation OUTSIDE
        ``lease`` (the prefill step emits the initial cache leaves) so
        their eventual ``release`` balances the books."""
        with self._lock:
            self.leases_active += n
            self.leases_peak = max(self.leases_peak, self.leases_active)

    def release(self, leaf: jax.Array, *, reuse: bool = True) -> None:
        """Return a leased buffer.  ``reuse=False`` retires it to the GC
        (zero-required leaves gain nothing from parking — their next
        lease allocates fresh zeros anyway) but still settles the lease.
        A buffer a program consumed (donated, so deleted) settles its lease
        and is never parked."""
        with self._lock:
            if reuse and not leaf.is_deleted():
                free = self._free.setdefault(
                    (tuple(leaf.shape), jnp.dtype(leaf.dtype).name), []
                )
                free.append(leaf)
                self.parked_bytes += leaf.nbytes
                if len(free) > self._MAX_PARKED:
                    self.parked_bytes -= free.pop(0).nbytes
            else:
                self.retired += 1
            self.leases_active -= 1
            self.released += 1

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "leases_active": self.leases_active,
                "leases_peak": self.leases_peak,
                "lease_hits": self.lease_hits,
                "lease_allocs": self.lease_allocs,
                "released": self.released,
                "retired": self.retired,
                "parked_bytes": self.parked_bytes,
            }


class VortexServer:
    """Batched LM serving with Vortex-bucketed dynamic shapes.

    The dynamic dims are the request batch size and the prompt length; both
    are padded to buckets before hitting the compiled prefill/decode
    executables.  The server owns (or is handed) an :class:`Engine`
    session; its sequence buckets are the engine's selection buckets.
    """

    def __init__(
        self,
        cfg,
        mesh,
        *,
        max_cache: int = 512,
        seed: int = 0,
        engine: Engine | None = None,
        prefill: str = "aot",
    ):
        if prefill not in ("aot", "chained"):
            raise ValueError(
                f"prefill must be 'aot' or 'chained', got {prefill!r}"
            )
        self.prefill = prefill
        self.cfg = cfg
        self.rules = make_rules(
            mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads
        )
        self.params = init_params(cfg, jax.random.PRNGKey(seed))
        self.max_cache = max_cache
        if engine is None:
            # The lattice is built for the attached chip (an unknown TPU
            # kind raises); on the CPU the server builds the v5e lattice,
            # so its native sublane granularity (16) quantizes the same
            # bucket set there.  impl is derived from the platform:
            # compiled Pallas kernels on the TPU, XLA executables on the CPU.
            platform = resolve_platform()
            engine = Engine(EngineConfig(
                hardware=(platform.hardware.name if platform.native_pallas
                          else "tpu_v5e"),
                backends=("mxu",),
            ))
        self.engine = engine
        # The token dim's bucket source: the model's GEMM signature
        # (N/K = d_model); the selector's M-buckets become our seq buckets.
        # Built via kernel_for, not engine.compile: this handle only ever
        # does bucket arithmetic (select/bucket/buckets), so the engine's
        # eager-precompile policy (precompile_m_max) must not fire for it —
        # the executables would never be dispatched.
        self._seq_op = CompiledOp(engine, engine.kernel_for(
            GemmWorkload(M=None, N=cfg.d_model, K=cfg.d_model)
        ))
        # The cache dim's bucket source: the decode-attention workload over
        # the model's head_dim — its kv buckets (== the kv buckets prefill
        # attention streams, see DecodeAttentionWorkload) are the cache
        # lengths the decode programs are compiled at.
        self._decode_op = CompiledOp(engine, engine.kernel_for(
            DecodeAttentionWorkload(seq=None, head_dim=cfg.resolved_head_dim)
        ))
        # ONE jit per program family; buckets are AOT lowered+compiled
        # through it, so each bucket pays exactly one real compilation and
        # the stats count compilations, not wrapper constructions.
        # Prefill jits are keyed by the emitted cache length (= the kv
        # bucket covering the seq bucket), decode jits by the cache length
        # they serve.
        self._prefill_jits: dict[int, Any] = {}
        self._prefill_exec: dict[tuple[int, int], jax.stages.Compiled] = {}
        self._decode_jits: dict[int, Any] = {}
        self._decode_exec: dict[tuple[int, int], jax.stages.Compiled] = {}
        # Mixed-progress programs: same jit family, pos lowered as a (bp,)
        # per-row vector — a DIFFERENT XLA artifact, cached separately so
        # the scalar-pos serial path keeps its own executables.
        self._decode_exec_vec: dict[tuple[int, int], jax.stages.Compiled] = {}
        # Growable cache leaves are leased from (and returned to) a shared
        # bucket pool instead of churning fresh allocations per growth.
        self.kv_pool = KVBucketPool()
        self.stats = {
            "prefill_compiles": 0, "bucket_hits": 0,
            "decode_compiles": 0, "decode_bucket_hits": 0,
            "chained_prefills": 0,
            "decode_inplace_launches": 0, "decode_restack_launches": 0,
        }
        # Lazy-chain prefill state: per-(bp, sp) alignment verdicts, the
        # unstacked per-layer params in scan order, and the head matrix.
        self._chain_aligned_cache: dict[tuple[int, int], bool] = {}
        self._attn_aligned_cache: dict[int, bool] = {}
        self._chain_layer_cache: list | None = None
        self._head_cache: jax.Array | None = None
        # Per-token decode accounting (the padding-free decode contract):
        # one launch per token, zero pad fallbacks, a stage copy only when
        # the cache grows into the next kv bucket.
        self.decode_stats = DispatchStats()

    # -- engine-owned bucketing ---------------------------------------------

    def seq_bucket(self, s: int) -> int:
        """The engine-selected padded size for a prompt length (capped by
        the cache length)."""
        return min(self._seq_op.bucket(s), self.max_cache)

    @staticmethod
    def batch_bucket(b: int) -> int:
        """Pow2 bucket for the request batch dim (see vortex.pow2_bucket:
        an auxiliary multiplier of the token dim, deliberately NOT lattice
        quantized — that would double-pad)."""
        return pow2_bucket(b)

    def seq_buckets(self, m_max: int | None = None) -> list[int]:
        """Every sequence bucket this server can emit — the engine's own
        reachable-bucket set, capped by the cache length."""
        m_max = self.max_cache if m_max is None else min(m_max, self.max_cache)
        return sorted({min(b, self.max_cache)
                       for b in self._seq_op.buckets(m_max)})

    # -- decode kv buckets --------------------------------------------------

    def kv_bucket(self, n: int) -> int:
        """The decode cache length covering ``n`` valid rows: the
        decode-attention workload's own kv bucket, capped by max_cache."""
        return min(self._decode_op.bucket(n), self.max_cache)

    def _grown_kv_bucket(self, kvb: int, needed: int) -> int:
        """The next cache length once ``needed`` rows outgrow ``kvb``:
        amortized doubling quantized to a kv bucket, so a long generation
        pays O(log) growth copies and the reachable bucket chain (what
        warmup must precompile) stays logarithmic — not one decode program
        per lattice breakpoint."""
        return self.kv_bucket(max(needed, 2 * kvb))

    def decode_buckets(
        self, *, m_max: int | None = None, max_new: int = 0
    ) -> list[int]:
        """Every cache length decode can run at for prompts up to
        ``m_max`` generating up to ``max_new`` tokens: the prefill-emitted
        buckets plus their doubling-growth chains."""
        m_max = self.max_cache if m_max is None else min(m_max, self.max_cache)
        out: set[int] = set()
        for sp in self.prefill_seq_buckets(m_max):
            kvb = self.kv_bucket(sp)
            out.add(kvb)
            limit = min(sp + max(max_new, 0), self.max_cache)
            while kvb < limit:
                kvb = self._grown_kv_bucket(kvb, kvb + 1)
                out.add(kvb)
        return sorted(out)

    # -- compiled-program cache ---------------------------------------------

    def _make_batch(self, bp: int, sp: int, tokens: np.ndarray | None = None):
        toks = np.zeros((bp, sp), np.int32)
        s = sp
        if tokens is not None:
            b, s = tokens.shape
            toks[:b, :s] = tokens
        # ``last``: the last REAL prompt position, whose logits predict the
        # first new token (the rows past it are bucket pad).
        batch = {
            "tokens": jnp.asarray(toks),
            "last": jnp.asarray(s - 1, jnp.int32),
        }
        if self.cfg.vision_prefix:
            batch["vision_embeds"] = jnp.zeros(
                (bp, self.cfg.vision_prefix, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype),
            )
        if self.cfg.encoder_decoder:
            batch["encoder_frames"] = jnp.zeros(
                (bp, self.cfg.encoder_seq, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype),
            )
        return batch

    def _prefill_exec_for(self, bp: int, sp: int, batch) -> "jax.stages.Compiled":
        key = (bp, sp)
        exe = self._prefill_exec.get(key)
        if exe is None:
            # Lower under the engine session: prefill attention inside the
            # model dispatches through the engine
            # (models/layers.attn_forward consults installed_engine()), so
            # the traced program embeds lattice-selected attention blocks
            # and the engine's executable cache is warmed at trace time.
            # The emitted cache is ALREADY kv-bucket shaped: decode starts
            # on the aligned path with zero copies.
            cache_len = self.kv_bucket(sp)
            pj = self._prefill_jits.get(cache_len)
            if pj is None:
                pj = jax.jit(make_prefill_step(self.cfg, self.rules, cache_len))
                self._prefill_jits[cache_len] = pj
            with self.engine.use():
                exe = pj.lower(self.params, batch).compile()
            self._prefill_exec[key] = exe
            self.stats["prefill_compiles"] += 1
        else:
            self.stats["bucket_hits"] += 1
        return exe

    def _decode_exec_for(self, bp: int, kvb: int) -> "jax.stages.Compiled":
        """The ONE AOT decode program for a (batch-bucket, cache-length)
        pair.  Lowering runs under the engine session: the in-model decode
        attention dispatches through the kv_len-masked decode workload at
        the bucket-aligned cache length, so the compiled step embeds the
        lattice-selected kv block and runs pad-free."""
        return self._hand_out(self._decode_exec, bp, kvb, ())

    def _decode_exec_vec_for(self, bp: int, kvb: int) -> "jax.stages.Compiled":
        """The mixed-progress decode program for a (batch-bucket,
        cache-length) pair: identical to ``_decode_exec_for`` except
        ``pos`` lowers as a ``(bp,)`` per-row i32 vector, so ONE launch
        advances rows sitting at DIFFERENT kv positions — the scheduler's
        batched step.  Shares the jit family (and the compile counters)
        with the scalar program; the compiled artifacts are distinct."""
        return self._hand_out(self._decode_exec_vec, bp, kvb, (bp,))

    def _hand_out(self, programs: dict, bp: int, kvb: int, pos_shape):
        """A decode program for a launch, counted by the path its cache
        update was compiled on: ``decode_inplace_launches`` (the stacked
        cache is updated in place) or ``decode_restack_launches``."""
        key = (bp, kvb)
        if key in programs:
            self.stats["decode_bucket_hits"] += 1
        exe = self._decode_program(programs, bp, kvb, pos_shape)
        inplace = decode_updates_in_place(self.cfg, self.rules, kvb)
        self.stats[
            "decode_inplace_launches" if inplace
            else "decode_restack_launches"
        ] += 1
        return exe

    def _decode_program(self, programs: dict, bp: int, kvb: int, pos_shape):
        """Compile (once) the decode program for ``(bp, kvb)`` with ``pos``
        of ``pos_shape`` into ``programs``.  The cache argument is donated:
        the program's output cache takes over the input's buffers, which
        are deleted by the call."""
        key = (bp, kvb)
        exe = programs.get(key)
        if exe is None:
            dj = self._decode_jits.get(kvb)
            if dj is None:
                dj = jax.jit(
                    make_decode_step(self.cfg, self.rules, cache_len=kvb),
                    donate_argnums=(1,),
                )
                self._decode_jits[kvb] = dj
            with self.engine.use():
                exe = dj.lower(
                    self.params,
                    abstract_cache(self.cfg, bp, kvb),
                    jax.ShapeDtypeStruct((bp, 1), jnp.int32),
                    jax.ShapeDtypeStruct(pos_shape, jnp.int32),
                ).compile()
            programs[key] = exe
            self.stats["decode_compiles"] += 1
        return exe

    # Which axis of each cache leaf is the cache-length dim (leaves carry a
    # leading stacked-groups axis); mamba state and encoder_out never grow.
    _CACHE_SEQ_AXIS = {"k": 3, "v": 3, "ckv": 2, "k_rope": 2}
    # Leaves every read of which goes through the kv_len-masked decode
    # workload: stale bytes past the extent are never consumed, so these
    # may lease RECYCLED pool buffers without zeroing.  MLA's ckv/k_rope
    # are absent — its absorbed decode masks scores but not 0*garbage in
    # the PV contraction, so those always lease fresh zeros.
    _POOLED_STALE_OK = ("k", "v")

    def _cache_kv_leaves(self, cache: dict):
        """(entry, name) for every growable (pool-managed) cache leaf."""
        for key, entry in cache.items():
            if key == "encoder_out":
                continue
            for name in entry:
                if name in self._CACHE_SEQ_AXIS:
                    yield entry, name

    def adopt_cache(self, cache: dict) -> None:
        """Register a prefill-emitted cache's growable leaves as active
        pool leases (they entered circulation outside ``lease``)."""
        self.kv_pool.adopt(sum(1 for _ in self._cache_kv_leaves(cache)))

    def release_cache(self, cache: dict, *, reuse: bool = True) -> None:
        """Return every growable leaf to the pool — request retirement
        (and the ``generate`` exception path) funds future leases.  Leaves
        a decode launch consumed (donated, then raised) settle their
        leases without being parked.  ``reuse=False`` retires every leaf
        instead: a caller that never leases these shapes again parks
        nothing."""
        for entry, name in self._cache_kv_leaves(cache):
            self.kv_pool.release(
                entry[name], reuse=reuse and name in self._POOLED_STALE_OK
            )

    def _grow_cache(
        self, cache: dict, new_len: int, *, reuse: bool = True
    ) -> dict:
        """Copy the cache into ``new_len``-long bucket buffers: ONE
        O(true-size) ``dynamic_update_slice`` per growing leaf, only at
        bucket transitions — never per token.  Buffers are LEASED from the
        kv pool (attention k/v reuse parked buffers as-is — their stale
        tails sit past kv_len and are never read; MLA's ckv/k_rope lease
        fresh zeros, see ``_POOLED_STALE_OK``) and the outgrown leaves are
        released back, so chained growth recycles instead of churning.

        Growth is TWO-PHASE for failure isolation: every new leaf is
        leased and copied first, and the outgrown leaves are released only
        once the whole cache grew.  A mid-grow failure (lease fault, OOM)
        releases the partial new set and re-raises with ``cache``
        untouched — the caller's settling ``finally`` then releases every
        ORIGINAL lease exactly once, never double-releasing a leaf this
        method already returned.  ``reuse=False`` retires the outgrown
        leaves instead of parking them (a cache that only grows never
        leases their shapes again).
        """
        st = self.decode_stats
        pool = self.kv_pool
        new_leases: list[tuple[jax.Array, bool]] = []
        old_leaves: list[tuple[jax.Array, bool]] = []
        out_cache: dict = {}
        try:
            for key, entry in cache.items():
                if key == "encoder_out":
                    out_cache[key] = entry
                    continue
                out = {}
                for name, leaf in entry.items():
                    ax = self._CACHE_SEQ_AXIS.get(name)
                    if ax is None or leaf.shape[ax] >= new_len:
                        out[name] = leaf
                        continue
                    shape = list(leaf.shape)
                    shape[ax] = new_len
                    stale_ok = name in self._POOLED_STALE_OK
                    buf = pool.lease(
                        tuple(shape), leaf.dtype, zero=not stale_ok
                    )
                    new_leases.append((buf, stale_ok))
                    out[name] = jax.lax.dynamic_update_slice(
                        buf, leaf, (0,) * leaf.ndim
                    )
                    old_leaves.append((leaf, stale_ok))
                out_cache[key] = out
        except BaseException:
            for buf, stale_ok in new_leases:
                pool.release(buf, reuse=stale_ok)
            raise
        for leaf, stale_ok in old_leaves:
            pool.release(leaf, reuse=reuse and stale_ok)
        st.stage_copies += len(old_leaves)
        return out_cache

    # -- lazy-handle chained prefill ----------------------------------------

    def _prefill_chained_supported(self) -> bool:
        """True when every layer of the architecture runs through the lazy
        handle chain (plain attn mixer, dense/none MLP, no cross-attention,
        no vision prefix / encoder stack)."""
        cfg = self.cfg
        if cfg.vision_prefix or cfg.encoder_decoder:
            return False
        return all(
            spec.mixer == "attn" and spec.mlp in ("dense", "none")
            and not spec.cross_attn
            for spec in cfg.pattern
        )

    def _attn_aligned(self, sp: int) -> bool:
        """True when prefill attention at sequence bucket ``sp`` runs on its
        own bucket: the attention bucket at sp is (sp, hd, sp) for every
        window kind, and the kv cache bucket covering sp is sp itself — so
        the dispatch traced into a prefill program pads nothing."""
        hit = self._attn_aligned_cache.get(sp)
        if hit is None:
            cfg = self.cfg
            hd = cfg.resolved_head_dim
            hit = self.kv_bucket(sp) == sp and all(
                self.engine.kernel_for(AttentionWorkload(
                    seq=None, head_dim=hd, causal=True,
                    window=window, softcap=cfg.attn_softcap,
                )).select(sp).bucket == (sp, hd, sp)
                for window in {
                    spec.window for spec in cfg.pattern
                    if spec.mixer == "attn"
                }
            )
            self._attn_aligned_cache[sp] = hit
        return hit

    def _chain_aligned(self, bp: int, sp: int) -> bool:
        """True when EVERY dispatch of a (bp, sp) chained prefill lands on
        its own bucket: each chain GEMM's selection at m = bp*sp pads to
        exactly bp*sp, and attention is aligned at sp (``_attn_aligned``)
        — so handles forward bucket-to-bucket with zero boundary copies end
        to end."""
        key = (bp, sp)
        hit = self._chain_aligned_cache.get(key)
        if hit is None:
            eng, m = self.engine, bp * sp
            hit = self._attn_aligned(sp) and all(
                eng.kernel_for(
                    GemmWorkload(M=None, N=n, K=k)
                ).select(m).padded_m == m
                for k, n in chain_gemm_sigs(self.cfg)
            )
            self._chain_aligned_cache[key] = hit
        return hit

    def _first_aligned(self, s: int, aligned) -> int:
        """The first engine bucket >= seq_bucket(s) that ``aligned``
        accepts, or seq_bucket(s) when none is (still correct, it just
        pays the pads or copies)."""
        base = self.seq_bucket(s)
        for sp in self.seq_buckets():
            if sp >= base and aligned(sp):
                return sp
        return base

    def prefill_seq_bucket(self, s: int) -> int:
        """The sequence bucket the whole-program prefill serves ``s`` at:
        the first engine bucket where its attention pads nothing.  The
        attention lattice's kv block is at least the lane width (128), so
        prompts shorter than that run every GEMM at the 128 bucket."""
        return self._first_aligned(s, self._attn_aligned)

    def prefill_seq_buckets(self, m_max: int | None = None) -> list[int]:
        """Every bucket the whole-program prefill serves prompts up to
        ``m_max`` at (what ``warmup`` compiles)."""
        return sorted({
            self.prefill_seq_bucket(s) for s in self.seq_buckets(m_max)
        })

    def chain_seq_bucket(self, s: int, bp: int = 1) -> int:
        """The sequence bucket a chained prefill serves ``s`` at: the first
        engine bucket where the whole chain is aligned (``_chain_aligned``)
        — a misaligned chain stays correct, it just pays counted boundary
        copies."""
        return self._first_aligned(
            s, functools.partial(self._chain_aligned, bp)
        )

    def _chain_layers(self) -> list:
        """(spec, params) per layer in scan execution order (group-major),
        unstacked once from the pos-stacked parameter tree."""
        if self._chain_layer_cache is None:
            cfg = self.cfg
            n_pos = len(cfg.pattern)
            layers = []
            for g in range(cfg.n_groups):
                for i in range(n_pos):
                    p = jax.tree_util.tree_map(
                        lambda t: t[g], self.params[f"pos{i}"]
                    )
                    layers.append((cfg.pattern[i], p))
            self._chain_layer_cache = layers
        return self._chain_layer_cache

    def _head(self) -> jax.Array:
        if self._head_cache is None:
            self._head_cache = (
                self.params["embed"].T if self.cfg.tie_embeddings
                else self.params["lm_head"]
            )
        return self._head_cache

    @staticmethod
    def _chain_cache_leaf(t, kvb: int):
        """One kv-cache leaf from a chain k/v projection: a fully-valid
        handle's bucket buffer is consumed DIRECTLY when it already has the
        cache length (zero copy); otherwise one dynamic_update_slice into
        zeros — bitwise what the AOT prefill's jnp.pad emits."""
        from repro.core.engine import LazyBucket

        if isinstance(t, LazyBucket):
            t = t.realize()  # identity for the chain's fully-valid handles
        if t.shape[2] == kvb:
            return t
        buf = jnp.zeros(t.shape[:2] + (kvb,) + t.shape[3:], t.dtype)
        return jax.lax.dynamic_update_slice(buf, t, (0,) * t.ndim)

    def prefill_chained(self, bp: int, sp: int, batch, *, eager: bool = False):
        """Whole-model prefill as a lazy handle chain: embed (plain ops) →
        per-layer ``block_forward_lazy`` → final norm / head / softcap /
        vocab mask via ``lazy_map`` — every engine boundary passes a
        LazyBucket, so at a chain-aligned ``sp`` nothing unstages between
        dispatches.  Returns ``(last_logits, cache)`` exactly like the AOT
        prefill step: last_logits at the last real prompt position
        ``batch["last"]`` (the chain's handles are fully valid to the bucket
        width, so that row is read straight from the buffer), cache leaves
        kv-bucket shaped.

        ``eager=True`` runs the IDENTICAL dispatch sequence on plain arrays
        (per-op stage/unstage) — the bit-identity reference the tests and
        the bench gate compare against."""
        from repro.core.engine import LazyBucket, lazy_map
        from repro.models.layers import (
            block_forward_lazy,
            lazy_matmul,
            norm,
        )

        cfg = self.cfg
        eng = self.engine
        lazy = not eager

        # Pre-block embedding pipeline, bitwise the model's forward().
        x = jnp.take(self.params["embed"], batch["tokens"], axis=0)
        if cfg.embed_scale:
            x = (
                x.astype(jnp.float32) * math.sqrt(cfg.d_model)
            ).astype(x.dtype)
        if not cfg.use_rope:
            p_idx = jnp.arange(sp).astype(jnp.float32)
            half = cfg.d_model // 2
            freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
            ang = p_idx[:, None] * freq
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
            x = x + pe[None].astype(x.dtype)
        positions = jnp.arange(sp)

        if lazy:
            x = LazyBucket(x, sp, 1)
        kvs = []
        for spec, p in self._chain_layers():
            x, kv = block_forward_lazy(
                eng, p, x, cfg, spec, positions=positions, lazy=lazy,
            )
            kvs.append(kv)

        x = lazy_map(lambda t: norm(t, self.params["final_norm"], cfg), x)
        logits = lazy_matmul(eng, x, self._head(), lazy=lazy)
        if cfg.logit_softcap is not None:
            c = cfg.logit_softcap
            logits = lazy_map(
                lambda t: (
                    jnp.tanh(t.astype(jnp.float32) / c) * c
                ).astype(t.dtype),
                logits,
            )
        if cfg.vocab_padded != cfg.vocab:
            logits = lazy_map(
                lambda t: jnp.where(
                    jax.lax.broadcasted_iota(
                        jnp.int32, t.shape, t.ndim - 1
                    ) < cfg.vocab,
                    t, -1e30,
                ),
                logits,
            )
        # The chain's handle is fully valid to the bucket width, so the
        # last real prompt row is read without forcing a slice.
        if isinstance(logits, LazyBucket):
            logits = logits.buffer
        last = logits[:, batch["last"]]

        kvb = self.kv_bucket(sp)
        n_pos = len(cfg.pattern)
        cache: dict[str, Any] = {}
        for i in range(n_pos):
            ks, vs = [], []
            for g in range(cfg.n_groups):
                kv = kvs[g * n_pos + i]
                ks.append(self._chain_cache_leaf(kv["k"], kvb))
                vs.append(self._chain_cache_leaf(kv["v"], kvb))
            cache[f"pos{i}"] = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
        return last, cache

    def warmup(
        self, *, max_batch: int = 1, m_max: int | None = None,
        max_new: int = 8,
    ) -> int:
        """Precompile before traffic: AOT compile the prefill program for
        every (batch-bucket, seq-bucket) pair up to ``max_batch``/``m_max``
        AND the decode program for every cache length those prompts can
        reach within ``max_new`` generated tokens (the doubling-growth
        bucket chains — see ``decode_buckets``).  The bucket sets are the
        engine's own (CompiledOp.buckets), and each AOT compile warms the
        engine's attention executables through the session — ``generate``
        pads every prompt to one of these buckets first, so this covers
        exactly the executables serving will hit.  Returns the number of
        programs compiled (prefill + decode).

        Direct-op serving (no model in between) warms with
        ``CompiledOp.precompile`` instead — see DESIGN.md §6."""
        m_max = self.max_cache if m_max is None else min(m_max, self.max_cache)
        compiled = 0
        bp = 1
        while True:
            for sp in self.prefill_seq_buckets(m_max):
                if (bp, sp) not in self._prefill_exec:
                    self._prefill_exec_for(bp, sp, self._make_batch(bp, sp))
                    compiled += 1
            for kvb in self.decode_buckets(m_max=m_max, max_new=max_new):
                if (bp, kvb) not in self._decode_exec:
                    self._decode_program(self._decode_exec, bp, kvb, ())
                    compiled += 1
            if bp >= pow2_bucket(max_batch):
                break
            bp *= 2
        return compiled

    def engine_dispatch_stats(self) -> dict[str, dict]:
        """Per-kind hot-path accounting from the engine session — launches,
        staging/unstaging copies, aligned vs unaligned calls, and how many
        calls ran padded (trace-time lowering) — PLUS the server's own
        per-token decode accounting under ``decode_step`` (the decode
        programs run outside the engine's eager dispatch, so their
        launches are counted here: one per token, a stage copy per cache
        growth, padded always 0).  The padding-free serving contract in
        one dict — what ops dashboards should scrape."""
        keep = (
            "calls", "launches", "aligned_calls", "unaligned_calls",
            "stage_copies", "unstage_copies", "padded_calls",
            "traced_calls", "forwarded", "realize_slices",
            "fallbacks", "quarantined",
        )
        estats = self.engine.stats()
        out = {
            kind: {k: s[k] for k in keep}
            for kind, s in estats.items()
            if kind != "calibration"  # engine-level section, not a kind
        }
        d = self.decode_stats.as_dict()
        out["decode_step"] = {k: d[k] for k in keep}
        # The kv-bucket pool's lease ledger (its OWN key set: lease
        # accounting, not dispatch counters) — ``leases_active`` must read
        # 0 at idle or a retirement path leaked buffers.
        out["kv_pool"] = self.kv_pool.stats()
        # Background-calibration counters (core/calibrate.py), engine-level
        # like kv_pool: applied/loaded tables, swaps, measurement time.
        out["calibration"] = estats["calibration"]
        return out

    # -- serving ------------------------------------------------------------

    def _prefill(self, tokens: np.ndarray):
        """Prefill a (b, s) prompt through the configured path: the batch
        bucket, the sequence bucket, the logits predicting the first new
        token, and the kv-bucket-shaped cache."""
        b, s = tokens.shape
        bp = self.batch_bucket(b)
        if self.prefill == "chained" and self._prefill_chained_supported():
            sp = self.chain_seq_bucket(s, bp)
            batch = self._make_batch(bp, sp, tokens)
            logits, cache = self.prefill_chained(bp, sp, batch)
            self.stats["chained_prefills"] += 1
        else:
            sp = self.prefill_seq_bucket(s)
            batch = self._make_batch(bp, sp, tokens)
            logits, cache = self._prefill_exec_for(bp, sp, batch)(
                self.params, batch
            )
        return bp, sp, logits, cache

    def _serve(self, tokens: np.ndarray, steps: int, forced=None):
        """Yield ``(logits, greedy)`` for the prefill, then for ``steps``
        decode launches: logits ``(bp, vocab)`` and their argmax ``(bp,)``.
        Step i feeds ``forced[:, i]`` when given (teacher forcing), else the
        previous greedy token."""
        b, s = tokens.shape
        if s + steps > self.max_cache:
            # Refuse loudly BEFORE any prefill work: past the cap the
            # cache cannot grow, the in-program dynamic_update_slice would
            # clamp its start and silently stomp the last KV row —
            # corrupted logits with no signal.  Same typed error as the
            # scheduler's admission-time rejection (launch/scheduler.py).
            raise CacheOverflowError(
                f"prompt_len {s} + max_new {steps + 1} needs "
                f"{s + steps} cache rows > max_cache "
                f"{self.max_cache}; raise max_cache or shorten the request"
            )
        bp, sp, logits, cache = self._prefill(tokens)
        pos = s - 1
        kvb = self.kv_bucket(sp)  # the prefill-emitted cache length
        st = self.decode_stats
        # The prefill-emitted leaves are pool leases from here on: the
        # finally arm settles them on retirement AND on any exception
        # mid-decode, so the pool's lease ledger can never leak.
        self.adopt_cache(cache)
        try:
            greedy = jnp.argmax(logits, -1)
            yield logits, greedy
            for i in range(steps):
                if forced is None:
                    tok = greedy
                else:
                    tok = np.zeros((bp,), np.int32)
                    tok[:b] = forced[:, i]
                pos += 1
                needed = pos + 1  # rows the cache must hold after this step
                st.calls += 1
                if needed > kvb and kvb < self.max_cache:
                    kvb = self._grown_kv_bucket(kvb, needed)
                    cache = self._grow_cache(cache, kvb)
                    st.unaligned_calls += 1
                else:
                    st.aligned_calls += 1
                logits, cache = self._decode_exec_for(bp, kvb)(
                    self.params, cache, jnp.asarray(tok)[:, None],
                    jnp.asarray(pos, jnp.int32),
                )
                st.launches += 1
                greedy = jnp.argmax(logits, -1)
                yield logits, greedy
        finally:
            self.release_cache(cache)

    def generate(self, req: Request) -> np.ndarray:
        """Greedy decode: ``(b, max_new)`` tokens for a ``(b, s)`` prompt."""
        b = req.tokens.shape[0]
        out = [
            np.asarray(greedy)
            for _, greedy in self._serve(req.tokens, req.max_new - 1)
        ]
        return np.stack(out, 1)[:b]  # (b, max_new)

    def score(self, tokens: np.ndarray, n_prompt: int) -> np.ndarray:
        """Teacher-forced logits along a given sequence: prefill
        ``tokens[:, :n_prompt]``, then feed each following token through
        the decode programs.  Returns ``(b, T - n_prompt + 1, vocab)`` f32:
        row j holds the logits predicting token ``n_prompt + j`` — what a
        full forward over ``tokens`` gives at position ``n_prompt - 1 + j``.
        """
        b = tokens.shape[0]
        out = [
            np.asarray(logits[:b], np.float32)
            for logits, _ in self._serve(
                tokens[:, :n_prompt], tokens.shape[1] - n_prompt,
                forced=tokens[:, n_prompt:],
            )
        ]
        return np.stack(out, 1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-gpt2-124m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--warmup", action="store_true",
        help="AOT-precompile every bucket before serving",
    )
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh()
    server = VortexServer(cfg, mesh, max_cache=256)
    if args.warmup:
        n = server.warmup(max_batch=8, m_max=64, max_new=args.max_new)
        print(f"warmup: {n} prefill+decode buckets AOT-compiled")
    rng = np.random.default_rng(args.seed)

    t0 = time.perf_counter()
    for i in range(args.requests):
        b = int(rng.integers(1, 9))
        s = int(rng.integers(4, 65))
        req = Request(
            tokens=rng.integers(0, cfg.vocab, (b, s)).astype(np.int32),
            max_new=args.max_new,
        )
        out = server.generate(req)
        print(f"req {i:3d}: batch={b:3d} prompt={s:3d} -> {out.shape}")
    dt = time.perf_counter() - t0
    print(
        f"{args.requests} dynamic requests in {dt:.1f}s; "
        f"compiles={server.stats['prefill_compiles']} "
        f"bucket_hits={server.stats['bucket_hits']} "
        f"decode_compiles={server.stats['decode_compiles']} "
        f"decode_bucket_hits={server.stats['decode_bucket_hits']}"
    )
    ds = server.decode_stats
    print(
        f"decode: tokens={ds.calls} launches={ds.launches} "
        f"growth_copies={ds.stage_copies} padded={ds.padded_calls}"
    )
    for kind, d in server.engine_dispatch_stats().items():
        if kind == "kv_pool":  # lease ledger, not dispatch counters
            print(
                f"kv_pool: leases_active={d['leases_active']} "
                f"leases_peak={d['leases_peak']} hits={d['lease_hits']} "
                f"allocs={d['lease_allocs']} released={d['released']}"
            )
            continue
        if kind == "calibration":  # engine-level counters, not a kind
            if d.get("enabled"):
                print(
                    f"calibration: mode={d['mode']} applied={d['applied']} "
                    f"loaded={d['loaded_from_disk']} swaps={d['table_swaps']} "
                    f"seconds={d['seconds']:.3f}"
                )
            continue
        print(
            f"engine/{kind}: launches={d['launches']} "
            f"stage_copies={d['stage_copies']} "
            f"unstage_copies={d['unstage_copies']} "
            f"padded={d['padded_calls']} traced={d['traced_calls']} "
            f"fallbacks={d['fallbacks']} quarantined={d['quarantined']}"
        )


if __name__ == "__main__":
    main()
