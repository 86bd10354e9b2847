"""Continuous batching on top of :class:`~repro.launch.serve.VortexServer`.

The serial server runs one request at a time: prefill, then one decode
launch per token with the whole batch at ONE position.  Under concurrent
traffic that leaves the batch-bucket dimension idle — every request pays
its own decode stream.  This module packs concurrent requests into that
dimension instead:

  * an ADMISSION QUEUE (``submit``) accepts requests from any thread,
    assigns ``request_id``s, and rejects requests that could never be
    served (``prompt + max_new - 1 > max_cache``, or more rows than the
    scheduler has slots) with a queue-level error AT SUBMIT TIME — not
    deep inside a decode loop;
  * a STEP SCHEDULER (``step``/``drain``) retires finished rows and
    admits queued prefills between steps, then advances every active row
    with ONE mixed-progress decode launch
    (``VortexServer._decode_exec_vec_for``): ``pos`` is a per-row i32
    vector, so rows sitting at different kv positions — fresh admits next
    to nearly-done generations — share the launch.  Free slots ride along
    at ``pos=0``: the program writes their (finite) k/v row 0 and attends
    over exactly that one masked row, so a retired slot costs one key of
    work and never reads stale pool bytes;
  * the KV state is ONE shared set of kv-bucket buffers LEASED from the
    server's :class:`~repro.launch.serve.KVBucketPool` — each admitted
    row's prefill cache is copied into its slot and the per-request
    buffers released back immediately, and when any row outgrows the
    bucket the shared cache grows through the pool
    (``VortexServer._grow_cache``) exactly like the serial path.  Each
    decode launch donates the shared leaves and hands back the updated
    ones: the program writes each row's new K/V into them in place.

Step-granular contract (asserted by tests/test_scheduler.py): one AOT
launch per batched decode step, and per-request outputs token-identical
to serial ``generate()`` on the same server.

Tracing: each step opens bare-named ``jax.profiler`` spans (``sched.step``,
``sched.admit``, ``sched.prefill``, ``sched.first_token``,
``sched.slot_copy``, ``sched.grow``, ``sched.decode``, ``sched.readback``,
``sched.emit``) and adds the same ``perf_counter`` stamps to the step's
entry in ``step_positions`` (see ``ContinuousScheduler``), so the trace and
the record agree.  With no profiler running a span costs a microsecond
or two; no span adds a device sync or a transfer.

Failure domains (DESIGN.md §11): a fault while admitting, growing, or
decoding resolves to a typed per-request error — ``drain()`` returns
tokens *or* a :class:`~repro.launch.serve.RequestError` per request id —
and never tears down the step loop; every failure path settles its pool
leases.  ``submit()`` adds backpressure: a bounded queue (``max_queue`` →
:class:`~repro.launch.serve.QueueFullError`) and per-request wall-clock
deadlines (``Request.deadline_s`` →
:class:`~repro.launch.serve.DeadlineExceeded`, the slots reused next
step).

Supported architectures are the uniformly-attention decoders (every
mixer ``attn``, no cross-attention / vision prefix / encoder stack): the
shared cache then holds only k/v leaves, whose every read goes through
the kv_len mask — the stale-tail pool contract.  MLA/mamba/encoder
architectures keep the serial path.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.serve import (
    CacheOverflowError,
    DeadlineExceeded,
    QueueFullError,
    Request,
    RequestError,
    VortexServer,
)
from repro.models.model import abstract_cache
from repro.runtime import faults
from repro.vortex import pow2_bucket

__all__ = ["ContinuousScheduler", "batched_decode_supported"]

# The phases whose time the host spends waiting on the device.
_BLOCKING = ("sched.first_token", "sched.readback")


def batched_decode_supported(cfg) -> bool:
    """True when the mixed-progress batched decode serves this arch: all
    mixers are plain attention (shared cache = k/v leaves only, every
    read kv_len-masked) and there is no cross-attention, vision prefix,
    or encoder stack feeding extra per-request state."""
    if cfg.vision_prefix or cfg.encoder_decoder:
        return False
    return all(
        spec.mixer == "attn" and not spec.cross_attn for spec in cfg.pattern
    )


class _Phase:
    """One span of a step: a profiler annotation ``name`` (``ids`` as its
    stats) around the block, and the block's ``perf_counter`` seconds
    added to ``rec["phases"][name]``.  ``t`` and ``seconds`` are the
    block's start stamp and length, taken inside the annotation."""

    __slots__ = ("rec", "name", "ann", "t", "seconds")

    def __init__(self, rec: dict, name: str, **ids):
        self.rec, self.name = rec, name
        self.ann = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self) -> "_Phase":
        self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self.t
        self.ann.__exit__(*exc)
        phases = self.rec["phases"]
        phases[self.name] = phases.get(self.name, 0.0) + self.seconds


@dataclasses.dataclass
class _Row:
    """One occupied batch slot: a single sequence of one request."""
    rid: int
    req_row: int        # which row of the request's (b, s) token block
    pos_next: int       # cache position the NEXT decode step writes
    remaining: int      # decode steps left (max_new - tokens emitted)
    last_tok: int       # feeds the next step's token vector
    out: list[int]      # generated tokens so far (prefill argmax first)
    max_new: int
    stop: int | None


class ContinuousScheduler:
    """Admission queue + mixed-progress step scheduler over a server.

    ``submit()`` is thread-safe and returns the assigned request id;
    ``step()``/``drain()`` must run on one scheduler thread.  ``drain()``
    returns ``{request_id: (b, max_new) int64 array | RequestError}`` for
    every request resolved since the previous drain — tokens on success,
    the typed error when the request's admission/growth/decode failed or
    its deadline expired.  ``close()`` releases the shared cache leases
    back to the pool (``leases_active`` returns to 0).

    ``max_queue`` bounds the admission queue (``submit`` raises
    :class:`QueueFullError` at capacity); None = unbounded.

    ``step_positions`` holds one dict per decode launch: ``kvb`` (the
    bucket it ran at), ``pos`` and ``slots`` (its active rows), ``t0`` and
    ``t1`` (``perf_counter`` at ``step()`` entry and return), ``phases``
    (seconds per span name, summed over the step), ``blocked_s`` (seconds
    in ``sched.first_token`` and ``sched.readback``, the host waiting on
    the device) and ``admits`` (one dict per admission of the step:
    ``rid``, ``prompt`` length, prefill ``bucket``, ``admit_s`` and
    ``queued_s``, admission start minus ``submit()``).  A step that ends
    without a launch (idle, or every row failed) records nothing, its
    admissions included; an admission that fails adds its time to
    ``phases`` and no ``admits`` entry.
    """

    def __init__(
        self,
        server: VortexServer,
        *,
        batch_rows: int = 8,
        max_queue: int | None = None,
    ):
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if not batched_decode_supported(server.cfg):
            raise ValueError(
                "continuous batching needs a uniformly-attention decoder "
                "(every mixer 'attn', no cross-attn/vision/encoder); "
                f"arch pattern {[s.mixer for s in server.cfg.pattern]} "
                "is served by the serial generate() path"
            )
        self.server = server
        self.batch_rows = pow2_bucket(batch_rows)
        self.max_queue = max_queue
        self._lock = threading.Lock()
        self._queue: list[Request] = []
        self._next_id = 0
        self._results: dict[int, np.ndarray | RequestError] = {}
        # Per-request assembly: (buffer, rows_outstanding).
        self._partial: dict[int, tuple[np.ndarray, int]] = {}
        # rid -> (absolute monotonic deadline, the request's deadline_s).
        self._deadlines: dict[int, tuple[float, float]] = {}
        # rid -> perf_counter at submit(), while the request is queued.
        self._submitted: dict[int, float] = {}
        self.rows: list[_Row | None] = [None] * self.batch_rows
        self.cache: dict | None = None
        self.kvb = 0
        self.stats = {
            "steps": 0, "admitted": 0, "retired": 0,
            "calibration_slices": 0, "request_errors": 0,
            "deadline_expired": 0,
        }
        # One record per launch (see the class docstring).
        self.step_positions: list[dict] = []

    # -- admission queue ----------------------------------------------------

    def submit(self, req: Request) -> int:
        """Queue a request, validating it AT ADMISSION: requests that
        could never complete fail here with a clear error instead of
        corrupting a decode loop later.  Thread-safe."""
        b, s = req.tokens.shape
        if b > self.batch_rows:
            raise ValueError(
                f"request has {b} rows but the scheduler batches "
                f"{self.batch_rows}; split the request or raise batch_rows"
            )
        if s + req.max_new - 1 > self.server.max_cache:
            # Same typed error as the serial ``generate()`` pre-prefill
            # check (launch/serve.py) — one overflow contract, two paths.
            raise CacheOverflowError(
                f"admission refused: prompt_len {s} + max_new "
                f"{req.max_new} needs {s + req.max_new - 1} cache rows > "
                f"max_cache {self.server.max_cache}; raise max_cache or "
                "shorten the request"
            )
        with self._lock:
            if (
                self.max_queue is not None
                and len(self._queue) >= self.max_queue
            ):
                raise QueueFullError(
                    f"admission queue is full ({self.max_queue} queued "
                    "requests); drain or retry after capacity frees up"
                )
            rid = self._next_id
            self._next_id += 1
            req = dataclasses.replace(req, request_id=rid)
            self._queue.append(req)
            self._submitted[rid] = time.perf_counter()
            if req.deadline_s is not None:
                self._deadlines[rid] = (
                    time.monotonic() + req.deadline_s, req.deadline_s
                )
        return rid

    # -- shared kv cache ----------------------------------------------------

    def _ensure_cache(self, kvb: int) -> None:
        """Lease the shared kv-bucket leaves (stale pool contents are fine:
        a slot row is only read after its prefill copy / decode write, and
        always through the kv_len mask)."""
        if self.cache is not None:
            return
        spec = abstract_cache(self.server.cfg, self.batch_rows, kvb)
        pool = self.server.kv_pool
        cache: dict = {}
        leased: list[jax.Array] = []
        # Lease incrementally and settle on failure: a fault partway
        # through (pool_lease injection, OOM) must not strand the leaves
        # already checked out — leases_active stays exact.
        try:
            for key, entry in spec.items():
                got = {}
                for n, leaf in entry.items():
                    buf = pool.lease(leaf.shape, leaf.dtype)
                    leased.append(buf)
                    got[n] = buf
                cache[key] = got
        except BaseException:
            for buf in leased:
                pool.release(buf)
            raise
        self.cache = cache
        self.kvb = kvb

    def _grow(self, new_kvb: int, rec: dict) -> None:
        assert self.cache is not None
        with _Phase(rec, "sched.grow", kvb=new_kvb):
            self.cache = self.server._grow_cache(self.cache, new_kvb)
        self.kvb = new_kvb

    def close(self) -> None:
        """Release the shared cache leases; idempotent, and a later
        submit/step re-leases lazily."""
        if self.cache is None:
            return
        self.server.release_cache(self.cache)
        self.cache = None
        self.kvb = 0

    def _copy_row(self, rcache: dict, r: int, slot: int) -> None:
        """One admitted sequence: its prefill-emitted cache row lands in
        the shared cache's slot row (per-leaf dynamic_update_slice; the
        request bucket may be shorter than the shared bucket — the slot
        row's tail past it stays stale, masked by kv_len)."""
        assert self.cache is not None
        for key, entry in self.cache.items():
            src = rcache[key]
            for name in entry:
                row = jax.lax.dynamic_slice_in_dim(src[name], r, 1, axis=1)
                entry[name] = jax.lax.dynamic_update_slice(
                    entry[name], row, (0, slot, 0, 0, 0)
                )

    # -- scheduling ---------------------------------------------------------

    def _free_slots(self) -> list[int]:
        return [i for i, row in enumerate(self.rows) if row is None]

    def _fail_request(
        self, rid: int, stage: str, exc: BaseException
    ) -> None:
        """Resolve EVERY row of one request to a typed error: seated rows
        are cleared (their slots reused next step), the partial output
        buffer dropped, and ``drain()`` returns the
        :class:`~repro.launch.serve.RequestError` instead of tokens.  The
        shared cache is untouched — other requests keep decoding."""
        for slot, row in enumerate(self.rows):
            if row is not None and row.rid == rid:
                self.rows[slot] = None
        self._partial.pop(rid, None)
        self._deadlines.pop(rid, None)
        err = exc if isinstance(exc, RequestError) else RequestError(
            rid, stage, f"{type(exc).__name__}: {exc}"
        )
        with self._lock:
            self._submitted.pop(rid, None)
            self._results[rid] = err
        if isinstance(err, DeadlineExceeded):
            self.stats["deadline_expired"] += 1
        else:
            self.stats["request_errors"] += 1

    def _expire_deadlines(self) -> bool:
        """Retire queued and active requests whose wall-clock deadline
        passed; True if anything expired (the tick did work)."""
        if not self._deadlines:
            return False
        now = time.monotonic()
        expired: list[tuple[int, float]] = []
        with self._lock:
            for req in list(self._queue):
                dl = self._deadlines.get(req.request_id)
                if dl is not None and now > dl[0]:
                    self._queue.remove(req)
                    expired.append((req.request_id, dl[1]))
        for rid in {row.rid for row in self.rows if row is not None}:
            dl = self._deadlines.get(rid)
            if dl is not None and now > dl[0]:
                expired.append((rid, dl[1]))
        for rid, deadline_s in expired:
            self._fail_request(
                rid, "deadline", DeadlineExceeded(rid, deadline_s)
            )
        return bool(expired)

    def _admit(self, req: Request, submitted: float, rec: dict) -> None:
        """Prefill ONE queued request through the server's serial prefill
        executables and seat its rows: per-row first token from the
        prefill argmax, cache rows copied into free slots, the transient
        per-request buffers released back to the pool.  ``submitted`` is
        its ``submit()`` stamp; the admission goes into ``rec``."""
        srv = self.server
        rid = req.request_id
        assert rid is not None
        b, s = req.tokens.shape
        sp = srv.prefill_seq_bucket(s)
        with _Phase(
            rec, "sched.admit", rid=rid, prompt=s, bucket=sp
        ) as admit:
            if faults.ACTIVE is not None:
                faults.ACTIVE.check("scheduler_step")
            bp = srv.batch_bucket(b)
            with _Phase(rec, "sched.prefill"):
                batch = srv._make_batch(bp, sp, req.tokens)
                logits, rcache = srv._prefill_exec_for(bp, sp, batch)(
                    srv.params, batch
                )
            srv.adopt_cache(rcache)
            try:
                with _Phase(rec, "sched.first_token"):
                    first = np.asarray(jnp.argmax(logits, -1))  # (bp,)
                kvb_req = srv.kv_bucket(sp)
                with _Phase(rec, "sched.slot_copy"):
                    self._ensure_cache(kvb_req)
                    if kvb_req > self.kvb:
                        self._grow(kvb_req, rec)
                    slots = self._free_slots()
                    self._partial[rid] = (
                        np.zeros((b, req.max_new), np.int64), b
                    )
                    for r in range(b):
                        slot = slots[r]
                        self._copy_row(rcache, r, slot)
                        tok = int(first[r])
                        self.rows[slot] = _Row(
                            rid=rid, req_row=r, pos_next=s,
                            remaining=req.max_new - 1, last_tok=tok,
                            out=[tok], max_new=req.max_new, stop=req.stop,
                        )
                        if req.stop is not None and tok == req.stop:
                            self.rows[slot].remaining = 0
            finally:
                srv.release_cache(rcache)
        rec["admits"].append({
            "rid": rid, "prompt": s, "bucket": sp,
            "admit_s": admit.seconds, "queued_s": admit.t - submitted,
        })
        self.stats["admitted"] += 1

    def _retire(self, slot: int) -> None:
        row = self.rows[slot]
        assert row is not None and row.remaining == 0
        out = row.out
        if len(out) < row.max_new:  # early stop: pad with the stop token
            out = out + [row.stop] * (row.max_new - len(out))
        buf, outstanding = self._partial[row.rid]
        buf[row.req_row] = out
        outstanding -= 1
        if outstanding:
            self._partial[row.rid] = (buf, outstanding)
        else:
            del self._partial[row.rid]
            self._deadlines.pop(row.rid, None)
            with self._lock:
                self._results[row.rid] = buf
        self.rows[slot] = None
        self.stats["retired"] += 1

    def step(self) -> bool:
        """One scheduler tick: retire finished rows, expire deadlines,
        admit every queued request that fits, then advance all active rows
        with EXACTLY ONE mixed-progress decode launch.  Returns False when
        fully idle.

        Failure isolation: an exception while admitting resolves THAT
        request to a ``RequestError``; one while growing fails only the
        rows that needed the larger bucket; one in the decode launch fails
        the rows that shared it (and, if the launch consumed the donated
        shared cache, drops it for the next admission to re-lease).
        Nothing propagates out of ``step()`` — the loop, the shared cache,
        and the lease ledger stay serviceable.
        """
        rec: dict = {"t0": time.perf_counter(), "phases": {}, "admits": []}
        with _Phase(rec, "sched.step"):
            worked = self._step(rec)
        rec["t1"] = time.perf_counter()
        rec["blocked_s"] = sum(rec["phases"].get(n, 0.0) for n in _BLOCKING)
        return worked

    def _step(self, rec: dict) -> bool:
        """The body of ``step()``; ``rec`` joins ``step_positions`` at the
        launch."""
        srv = self.server
        worked = False
        for slot, row in enumerate(self.rows):
            if row is not None and row.remaining == 0:
                self._retire(slot)
                worked = True
        worked |= self._expire_deadlines()
        while True:
            with self._lock:
                req = (
                    self._queue.pop(0)
                    if self._queue
                    and self._queue[0].tokens.shape[0]
                    <= len(self._free_slots())
                    else None
                )
                if req is not None:
                    submitted = self._submitted.pop(req.request_id)
            if req is None:
                break
            try:
                self._admit(req, submitted, rec)
            except Exception as exc:
                assert req.request_id is not None
                self._fail_request(req.request_id, "admit", exc)
            worked = True
            # A stop token in the prefill argmax retires without a step.
            for slot, row in enumerate(self.rows):
                if row is not None and row.remaining == 0:
                    self._retire(slot)

        active = [
            (slot, row) for slot, row in enumerate(self.rows)
            if row is not None
        ]
        if not active:
            # Fully idle tick: donate one budgeted slice to the engine's
            # background calibrator (config.calibration="on-idle").  The
            # donation deliberately does NOT count as work — drain()'s
            # termination depends only on request progress, so a pending
            # calibration never keeps drain() spinning.
            self._donate_idle_slice()
            return worked
        assert self.cache is not None

        needed = max(row.pos_next + 1 for _, row in active)
        if needed > self.kvb and self.kvb < srv.max_cache:
            try:
                self._grow(srv._grown_kv_bucket(self.kvb, needed), rec)
            except Exception as exc:
                # Two-phase growth left the shared cache (and every lease)
                # untouched — fail exactly the rows that no longer fit the
                # current bucket; everything else decodes next tick.
                stuck = {
                    row.rid for _, row in active
                    if row.pos_next + 1 > self.kvb
                }
                for rid in stuck:
                    self._fail_request(rid, "grow", exc)
                return True

        try:
            with _Phase(rec, "sched.decode", rows=len(active), kvb=self.kvb):
                # Free slots decode at pos 0: their k/v row 0 is freshly
                # written by this very launch (finite), and kv_len = 1
                # reads only it.
                tok = np.zeros((self.batch_rows, 1), np.int32)
                pos = np.zeros((self.batch_rows,), np.int32)
                for slot, row in active:
                    tok[slot, 0] = row.last_tok
                    pos[slot] = row.pos_next
                if faults.ACTIVE is not None:
                    faults.ACTIVE.check("scheduler_step")
                exe = srv._decode_exec_vec_for(self.batch_rows, self.kvb)
                logits, self.cache = exe(
                    srv.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(pos),
                )
        except Exception as exc:
            # The launch raised before the cache assignment.  The program
            # donates the cache, so a launch that ran consumed the shared
            # leaves: drop them (settling their leases) and let the next
            # admission lease afresh.  Otherwise they are the pre-step
            # state.  Either way every row that shared this launch
            # resolves to a typed error.
            if any(
                leaf.is_deleted()
                for entry in self.cache.values() for leaf in entry.values()
            ):
                self.close()
            for rid in {row.rid for _, row in active}:
                self._fail_request(rid, "decode", exc)
            return True
        self.stats["steps"] += 1
        rec.update(
            kvb=self.kvb,
            pos=np.asarray([row.pos_next for _, row in active]),
            slots=np.asarray([slot for slot, _ in active]),
        )
        self.step_positions.append(rec)
        with _Phase(rec, "sched.readback"):
            nxt = np.asarray(jnp.argmax(logits, -1))  # (batch_rows,)
        with _Phase(rec, "sched.emit"):
            for slot, row in active:
                t = int(nxt[slot])
                row.out.append(t)
                row.last_tok = t
                row.pos_next += 1
                row.remaining -= 1
                if row.stop is not None and t == row.stop:
                    row.remaining = 0
        return True

    def _donate_idle_slice(self) -> None:
        """With no queued requests and no active rows, give the engine's
        background calibrator one budgeted measurement slice (bounded by
        ``EngineConfig.calibration_budget_s``).  No-op when calibration is
        off or nothing is pending; never raises into the serving loop."""
        engine = getattr(self.server, "engine", None)
        cal = getattr(engine, "calibrator", None)
        if cal is None:
            return
        with self._lock:
            if self._queue:
                return
        try:
            if cal.pending():
                cal.run_slice()
                self.stats["calibration_slices"] += 1
        except Exception:
            pass

    def drain(self) -> dict[int, np.ndarray | RequestError]:
        """Run steps until queue and slots are empty; return (and clear)
        the results resolved since the last drain — a ``(b, max_new)``
        token array per completed request, or the
        :class:`~repro.launch.serve.RequestError` that resolved it.
        Failed requests free their slots immediately, so drain always
        terminates even when every step faults."""
        while True:
            worked = self.step()
            with self._lock:
                queued = bool(self._queue)
            if not worked and not queued and not any(self.rows):
                break
        with self._lock:
            out = self._results
            self._results = {}
        return out
