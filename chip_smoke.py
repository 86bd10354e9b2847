#!/usr/bin/env python3
"""Bring-up check: serve paper-gpt2-124m at full width on one TPU chip.

Drives the main path once, in this one process, through the entry points a
user calls: ``VortexServer.generate()`` (one AOT prefill program per
bucket, then the decode programs), then ``ContinuousScheduler`` with
concurrent requests, then the ``vortex.ops`` kernels directly.  The model
is the published width (12 layers, d_model 768, 12 heads, vocab 50257,
bf16) with random weights from ``SEED`` and GPT-2's context,
``max_cache=1024``.  It checks that:

  * the engine runs compiled Pallas kernels (``impl="pallas"``, not
    interpreted), and the prefill/decode programs hold ``tpu_custom_call``;
  * served logits (prefill, and teacher-forced decode) match the
    sessionless inline forward of the same params on the same chip;
  * each direct op matches its ``kernels/ref.py`` oracle on unseen extents;
  * no scheduler request ends in ``RequestError``;
  * every engine kind shows zero fallbacks, quarantines and padded calls.

Usage::

    python3 chip_smoke.py                  # needs one TPU chip
    python3 chip_smoke.py --cpu-rehearsal  # smoke size on the CPU, Pallas
                                           # interpreted; claims no TPU

The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase, or no TPU without ``--cpu-rehearsal``, exits non-zero
without that line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Served vs sessionless-reference logits: both run the same bf16 params on
# the same chip, but round intermediates in different orders (Pallas tiles
# vs XLA fusions).  Bound relative to the logit scale.
LOGIT_REL_BOUND = 5e-2
# Direct ops vs their f32-accumulating oracles, both rounded to bf16.
OP_REL_BOUND = 2e-2
# Seeds the params, the request tokens and the direct ops' inputs.
SEED = 0


@dataclasses.dataclass(frozen=True)
class Size:
    arch_cfg: object
    moe_cfg: object
    max_cache: int
    max_new: int
    requests: tuple  # (batch, prompt_len) per generate() request
    sched: tuple     # (prompt_len, max_new) per scheduler request
    checked: tuple   # indices into ``requests`` whose logits are checked
    gemm_m: tuple    # unseen extents for the direct gemm calls
    attn_seq: int
    decode_kv: tuple  # per-row kv_len of the direct decode call
    moe_cap: int      # capacity rows per expert of the direct grouped gemm


def full_size() -> Size:
    from repro.configs import granite_moe_1b, paper_gpt2

    return Size(
        arch_cfg=paper_gpt2.CONFIG, moe_cfg=granite_moe_1b.CONFIG,
        max_cache=1024, max_new=16,
        requests=((1, 17), (8, 255), (1, 511), (4, 700), (2, 1000), (3, 90)),
        sched=((17, 16), (511, 8), (1000, 4), (17, 12), (511, 16),
               (1000, 6), (17, 10), (511, 16)),
        checked=(0, 4), gemm_m=(37, 300, 1000), attn_seq=333,
        decode_kv=(700, 411, 1), moe_cap=150,
    )


def rehearsal_size() -> Size:
    from repro.configs import granite_moe_1b, paper_gpt2

    return Size(
        arch_cfg=paper_gpt2.SMOKE, moe_cfg=granite_moe_1b.SMOKE,
        max_cache=128, max_new=4,
        requests=((1, 5), (8, 17), (1, 33), (4, 50), (2, 100), (3, 9)),
        sched=((5, 4), (33, 2), (100, 3), (5, 4)),
        checked=(0, 4), gemm_m=(7, 45), attn_seq=37, decode_kv=(50, 9, 1),
        moe_cap=21,
    )


class PhaseError(RuntimeError):
    pass


@contextlib.contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    print(f"[{name}] start", flush=True)
    try:
        yield
    except Exception as e:
        print(f"[{name}] FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        raise
    times[name] = time.perf_counter() - t0
    print(f"[{name}] done in {times[name]:.3f}s", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def rel_err(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-6))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run at smoke size on the CPU (Pallas in interpret mode)",
    )
    args = ap.parse_args()

    import jax

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from repro import vortex
    from repro.core.hardware import resolve_platform
    from repro.kernels.gemm import interpret_pallas
    from repro.kernels.ref import ref_attention, ref_gemm, ref_grouped_gemm
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.launch.scheduler import ContinuousScheduler
    from repro.launch.serve import Request, RequestError, VortexServer
    from repro.models.model import forward
    from repro.models.params import count_params
    from repro.vortex import Engine, EngineConfig

    devs = jax.devices()
    dev = devs[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs),
    }
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"no TPU: JAX found {dev.platform!r} devices; this check "
              "runs on a TPU chip (or pass --cpu-rehearsal)", file=sys.stderr)
        return 2

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    size = rehearsal_size() if args.cpu_rehearsal else full_size()
    cfg = size.arch_cfg
    times: dict[str, float] = {}
    rng = np.random.default_rng(SEED)

    with phase("build", times):
        # In-memory denylist: a stale quarantine list on disk must not hide
        # candidates.  impl is derived from the platform (Pallas, compiled
        # natively on the TPU); the rehearsal asks for Pallas interpreted.
        engine = Engine(EngineConfig(
            hardware=("tpu_v5e" if args.cpu_rehearsal
                      else resolve_platform().hardware.name),
            backends=("mxu",),
            impl="pallas" if args.cpu_rehearsal else None,
            denylist_persist=False,
        ))
        interp = interpret_pallas()
        print(f"engine: hardware={engine.config.hardware} "
              f"impl={engine.config.impl} interpret={interp}")
        check(engine.config.impl == "pallas", "engine does not run Pallas")
        check(args.cpu_rehearsal or not interp, "Pallas interpreted on TPU")
        server = VortexServer(
            cfg, make_host_mesh(), max_cache=size.max_cache,
            seed=SEED, engine=engine,
        )
        jax.block_until_ready(server.params)
        print(f"model: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
              f"heads={cfg.n_heads} vocab={cfg.vocab} dtype={cfg.dtype} "
              f"params={count_params(cfg)} max_cache={size.max_cache}")

    def requests():
        return [
            Request(
                tokens=rng.integers(0, cfg.vocab, (b, s)).astype(np.int32),
                max_new=size.max_new,
            )
            for b, s in size.requests
        ]

    def sched_requests():
        return [
            Request(
                tokens=rng.integers(0, cfg.vocab, (1, s)).astype(np.int32),
                max_new=n,
            )
            for s, n in size.sched
        ]

    def run_scheduler(reqs):
        sched = ContinuousScheduler(server, batch_rows=8)
        ids = [sched.submit(r) for r in reqs]
        results = sched.drain()
        sched.close()
        return sched, [results[i] for i in ids]

    with phase("warmup", times):
        # Compile exactly the buckets the requests below touch, by serving
        # each request shape once, then the scheduler's admissions and
        # mixed-progress decode steps.
        for req in requests():
            server.generate(req)
        run_scheduler(sched_requests())
        print(f"warmup: prefill_compiles={server.stats['prefill_compiles']} "
              f"decode_compiles={server.stats['decode_compiles']}")

    with phase("serve", times):
        for (b, s), req in zip(size.requests, requests()):
            t0 = time.perf_counter()
            out = server.generate(req)
            dt = time.perf_counter() - t0
            check(out.shape == (b, size.max_new), f"shape {out.shape}")
            check(bool(((out >= 0) & (out < cfg.vocab)).all()),
                  "token outside the vocabulary")
            print(f"  generate batch={b} prompt={s} "
                  f"max_new={size.max_new}: {dt:.3f}s")

    with phase("scheduler", times):
        sched, results = run_scheduler(sched_requests())
        for (s, n), res in zip(size.sched, results):
            check(not isinstance(res, RequestError), f"request failed: {res}")
            check(res.shape == (1, n), f"scheduler output shape {res.shape}")
        leases = server.kv_pool.stats()["leases_active"]
        check(leases == 0, f"kv pool leaked {leases} leases")
        print(f"scheduler: {len(results)} requests, "
              f"steps={sched.stats['steps']} "
              f"request_errors={sched.stats['request_errors']}")

    with phase("logits_vs_reference", times):
        # The sessionless inline forward (no engine installed: XLA matmuls
        # and XLA chunked attention) of the same params on the same chip.
        @functools.partial(jax.jit, static_argnums=2)
        def reference(params, tokens, length):
            return forward(
                cfg, server.rules, params, tokens, mode="prefill",
                cache_len=length,
            )[0]

        for i in size.checked:
            b, s = size.requests[i]
            n_dec = size.max_new - 1
            toks = rng.integers(0, cfg.vocab, (b, s + n_dec)).astype(np.int32)
            ref = reference(server.params, jnp.asarray(toks), s + n_dec)
            ref = np.asarray(ref[:, s - 1:, :cfg.vocab], np.float32)
            ref_max = float(np.max(np.abs(ref)))
            got = server.score(toks, s)[..., :cfg.vocab]
            e_pre = float(np.max(np.abs(got[:, 0] - ref[:, 0])))
            e_dec = float(np.max(np.abs(got[:, 1:] - ref[:, 1:])))
            bound = LOGIT_REL_BOUND * max(1.0, ref_max)
            print(f"  logits batch={b} prompt={s}: "
                  f"max_abs_err prefill={e_pre:.6g} "
                  f"decode({n_dec} teacher-forced)={e_dec:.6g} "
                  f"max|ref|={ref_max:.6g} bound={bound:.6g}")
            check(max(e_pre, e_dec) <= bound,
                  f"logits off the reference by {max(e_pre, e_dec)}")

    with phase("ops_vs_oracle", times):
        key = jax.random.PRNGKey(SEED)
        d, hd, h = cfg.d_model, cfg.resolved_head_dim, cfg.n_heads
        bf16 = jnp.bfloat16

        def rand(shape, i):
            return jax.random.normal(jax.random.fold_in(key, i), shape, bf16)

        moe = size.moe_cfg
        E, dm, df = moe.moe.num_experts, moe.d_model, moe.moe.d_ff_expert
        cap = size.moe_cap
        counts = jnp.asarray(rng.integers(0, cap + 1, (E,)), jnp.int32)
        kvl = jnp.asarray(size.decode_kv, jnp.int32)
        nb = len(size.decode_kv)
        skv = max(size.decode_kv)
        cases = []
        for j, m in enumerate(size.gemm_m):
            for k, n in ((d, cfg.d_ff), (d, cfg.vocab_padded)):
                a, w = rand((m, k), 10 * j), rand((k, n), 10 * j + 1)
                cases.append((f"gemm m={m} k={k} n={n}",
                              lambda a=a, w=w: vortex.ops.gemm(a, w),
                              lambda a=a, w=w: ref_gemm(a, w)))
        q = rand((2, h, size.attn_seq, hd), 100)
        kk = rand((2, h, size.attn_seq, hd), 101)
        vv = rand((2, h, size.attn_seq, hd), 102)
        cases.append((f"attention b=2 h={h} seq={size.attn_seq} d={hd}",
                      lambda: vortex.ops.attention(q, kk, vv),
                      lambda: ref_attention(q, kk, vv)))
        dq = rand((nb, h, 1, hd), 103)
        dk, dv = rand((nb, h, skv, hd), 104), rand((nb, h, skv, hd), 105)
        cases.append((f"decode_attention kv_len={size.decode_kv}",
                      lambda: vortex.ops.decode_attention(dq, dk, dv, kvl),
                      lambda: ref_attention(dq, dk, dv, causal=False,
                                            offset=kvl - 1, kv_len=kvl)))
        x, wg = rand((E, cap, dm), 106), rand((E, dm, df), 107)
        cases.append((f"grouped_gemm E={E} cap={cap} k={dm} n={df}",
                      lambda: vortex.ops.grouped_gemm(x, wg, counts),
                      lambda: ref_grouped_gemm(x, wg, counts)))
        with engine.use():
            for name, run, oracle in cases:
                err = rel_err(run(), oracle())
                print(f"  {name}: max_abs_err/max|ref|={err:.6g} "
                      f"bound={OP_REL_BOUND}")
                check(err <= OP_REL_BOUND, f"{name} off its oracle")

    with phase("counters", times):
        stats = server.engine_dispatch_stats()
        kinds = [k for k in stats if k not in ("kv_pool", "calibration")]
        for kind in kinds:
            st = stats[kind]
            print(f"  {kind}: calls={st['calls']} launches={st['launches']} "
                  f"traced={st['traced_calls']} "
                  f"stage_copies={st['stage_copies']} "
                  f"padded={st['padded_calls']} fallbacks={st['fallbacks']} "
                  f"quarantined={st['quarantined']}")
            for key_ in ("fallbacks", "quarantined", "padded_calls"):
                check(st[key_] == 0, f"{kind}: {key_}={st[key_]}")
        want = {"gemm", "attention", "decode_attention", "grouped_gemm"}
        check(want <= set(kinds), f"engine kinds {kinds} miss {want}")
        programs = [
            *server._prefill_exec.values(), *server._decode_exec.values(),
            *server._decode_exec_vec.values(),
        ]
        with_kernel = sum("tpu_custom_call" in p.as_text() for p in programs)
        print(f"  compiled prefill/decode programs: {len(programs)}, "
              f"with tpu_custom_call: {with_kernel}")
        if not args.cpu_rehearsal:
            check(with_kernel == len(programs),
                  "a prefill/decode program runs no Pallas kernel")

    print("times: " + " ".join(f"{k}={v:.3f}s" for k, v in times.items()))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
