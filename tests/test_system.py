"""End-to-end system behaviour: training convergence, microbatch
equivalence, paper-claim mechanisms (off-sample robustness, compile-time
gap), and the dynamic serving driver."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import GemmWorkload, HOST_CPU, VortexKernel
from repro.core.baselines import SampleDrivenCompiler, VendorBaseline
from repro.data.pipeline import SyntheticLMDataset
from repro.models.params import init_params
from repro.models.partitioning import make_rules
from repro.models.registry import get_smoke_config
from repro.optim.adamw import adamw_init
from repro.train.step import TrainHParams, make_train_step


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_training_loss_decreases(mesh):
    """~40 steps on the GPT-2-smoke config must fit the synthetic stream."""
    cfg = get_smoke_config("paper-gpt2-124m")
    rules = make_rules(mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    hp = TrainHParams(base_lr=1e-2, warmup_steps=10, total_steps=60,
                      num_microbatches=1)
    step = jax.jit(make_train_step(cfg, rules, hp))
    data = SyntheticLMDataset(cfg.vocab, seq_len=32, global_batch=16)
    losses = []
    for i in range(60):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 1.0, losses[::8]


def test_microbatch_accumulation_matches_full_batch(mesh):
    """num_microbatches=4 must produce (numerically close) the same update
    as a single full batch."""
    cfg = get_smoke_config("paper-gpt2-124m")
    rules = make_rules(mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
    params = init_params(cfg, jax.random.PRNGKey(1))
    data = SyntheticLMDataset(cfg.vocab, seq_len=16, global_batch=8)
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

    outs = {}
    for mb in (1, 4):
        hp = TrainHParams(num_microbatches=mb, total_steps=10,
                          warmup_steps=1)
        step = jax.jit(make_train_step(cfg, rules, hp))
        p2, _, m = step(params, adamw_init(params), batch)
        outs[mb] = (p2, float(m["loss"]))
    assert outs[1][1] == pytest.approx(outs[4][1], rel=2e-2)
    for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-3,
        )


def test_off_sample_robustness_mechanism():
    """Paper Fig. 3 / Table 6 mechanism: the sample-driven baseline pads
    off-sample shapes to its sample grid; Vortex's lattice bounds padding
    everywhere.  Compare padded-M waste directly (hardware-independent)."""
    wl = GemmWorkload(M=None, N=256, K=256)
    vortex = VortexKernel(HOST_CPU, wl, empirical_levels=())
    sampled = SampleDrivenCompiler(
        HOST_CPU, wl, samples=[128, 192, 256], search_budget=2, repeats=1
    )
    worst_vortex, worst_sampled = 0.0, 0.0
    for m in range(1, 300, 7):
        v = vortex.select(m).padded_m / m
        s = sampled.padded_m(m) / m
        worst_vortex = max(worst_vortex, v)
        worst_sampled = max(worst_sampled, s)
    # The sample-driven worst case (small M routed to sample 128) is far
    # worse than the lattice-bounded worst case.
    assert worst_sampled > worst_vortex


def test_offline_compile_time_gap():
    """Paper §7.4 mechanism: Vortex's sample-free offline stage must be much
    cheaper than tuning micro-kernels per sample on real hardware."""
    wl = GemmWorkload(M=None, N=128, K=128)
    t0 = time.perf_counter()
    vortex = VortexKernel(HOST_CPU, wl, empirical_levels=())
    vortex_s = time.perf_counter() - t0
    sampled = SampleDrivenCompiler(
        HOST_CPU, wl, samples=[32, 64, 96, 128], search_budget=4, repeats=2
    )
    assert sampled.tuning_seconds > vortex_s
    assert vortex.offline_stats.num_candidates > 0


def test_vendor_baseline_correctness():
    wl = GemmWorkload(M=None, N=64, K=32)
    vendor = VendorBaseline(wl)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(17, 32)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(vendor(a, b)), np.asarray(a) @ np.asarray(b), rtol=1e-4
    )


def test_dynamic_serving_end_to_end(mesh):
    """The serving driver handles shape-diverse requests with a bounded
    executable cache (Vortex bucketing)."""
    from repro.launch.serve import Request, VortexServer

    cfg = get_smoke_config("paper-gpt2-124m")
    server = VortexServer(cfg, mesh, max_cache=256)
    rng = np.random.default_rng(0)
    shapes = [
        (1, 5), (2, 9), (2, 12), (1, 14), (3, 30), (4, 60), (1, 130),
        (2, 200),
    ]
    for (b, s) in shapes:
        out = server.generate(Request(
            tokens=rng.integers(0, cfg.vocab, (b, s)).astype(np.int32),
            max_new=2,
        ))
        assert out.shape == (b, 2)
    # Distinct request shapes must share a smaller bucket set, and prompts
    # past the first attention-aligned bucket get a bucket of their own.
    assert server.stats["prefill_compiles"] < len(shapes)
    assert len({sp for _, sp in server._prefill_exec}) > 1


def test_server_buckets_are_engine_selector_buckets(mesh):
    """Acceptance (ISSUE 3): the server's sequence buckets must BE the
    engine selector's lattice buckets (`selections_upto`) — no second,
    hand-rolled bucketing scheme beside the selection table."""
    from repro.launch.serve import VortexServer

    cfg = get_smoke_config("paper-gpt2-124m")
    server = VortexServer(cfg, mesh, max_cache=128)
    selector = server._seq_op.kernel.selector
    expect = sorted({
        min(sel.padded_m, 128) for sel in selector.selections_upto(128)
    })
    assert server.seq_buckets() == expect
    for s in range(1, 129):
        assert server.seq_bucket(s) == min(selector.select(s).padded_m, 128)


def test_server_warmup_precompiles_buckets(mesh):
    """After warmup, in-range requests are all bucket hits: zero prefill
    AND zero decode compilations at serving time."""
    from repro.launch.serve import Request, VortexServer

    cfg = get_smoke_config("paper-gpt2-124m")
    server = VortexServer(cfg, mesh, max_cache=64)
    n = server.warmup(max_batch=2, m_max=64, max_new=4)
    n_prefill = server.stats["prefill_compiles"]
    n_decode = server.stats["decode_compiles"]
    assert n == n_prefill + n_decode
    assert n_prefill > 0 and n_decode > 0
    rng = np.random.default_rng(3)
    for (b, s) in [(1, 5), (2, 17), (1, 33)]:
        out = server.generate(Request(
            tokens=rng.integers(0, cfg.vocab, (b, s)).astype(np.int32),
            max_new=2,
        ))
        assert out.shape == (b, 2)
    assert server.stats["prefill_compiles"] == n_prefill  # nothing new
    assert server.stats["decode_compiles"] == n_decode
