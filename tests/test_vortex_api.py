"""The repro.vortex public API: registry-driven ops (a workload registered
in THIS file is served with no engine edits), contextvar-scoped engine
sessions (nesting, exception restore, thread isolation), CompiledOp
handles, EngineConfig, precompile diagnostics, and the deprecation shims'
parity contract (bit-identical outputs, identical cache keys)."""
import dataclasses
import threading
import types
from typing import ClassVar

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import vortex
from repro.core import GemmWorkload, PrecompileError, AttentionWorkload
from repro.core.hardware import resolve_platform
from repro.core.workloads import WORKLOADS
from repro.kernels.gemm import interpret_pallas
from repro.kernels.ref import ref_attention, ref_conv2d, ref_gemm
from repro.vortex import (
    CompiledOp,
    Engine,
    EngineConfig,
    VortexDeprecationWarning,
)

RNG = np.random.default_rng(11)


def _arr(shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


def _engine():
    return Engine("host_cpu", empirical_levels=())


# ---------------------------------------------------------------------------
# Registry-driven ops: @register_workload alone exposes vortex.ops.<kind>
# ---------------------------------------------------------------------------


def test_registered_toy_workload_served_with_no_engine_edits():
    """Acceptance: registering a workload in a TEST exposes a working
    vortex.ops.<kind> handle — no edits to any engine module."""

    @vortex.register_workload
    @dataclasses.dataclass(frozen=True)
    class DoubledGemm(GemmWorkload):
        """2 * (A @ B): distinct numerics so a routing mixup would show."""

        kind: ClassVar[str] = "doubled_gemm_toy"

        def build_executable(self, sel, *, impl, vmem_limit_bytes=None):
            inner = GemmWorkload.build_executable(
                self, sel, impl=impl, vmem_limit_bytes=vmem_limit_bytes
            )

            # The staging contract: the fused executable takes the bucket
            # view plus the runtime-extent scalars (here gemm's m_true).
            def fn(a, b, m_true):
                return 2.0 * inner(a, b, m_true)

            return fn

    try:
        assert "doubled_gemm_toy" in WORKLOADS
        a, b = _arr((13, 32)), _arr((32, 24))
        with vortex.use(_engine()) as eng:
            out = vortex.ops.doubled_gemm_toy(a, b)
            np.testing.assert_allclose(
                np.asarray(out), 2.0 * np.asarray(ref_gemm(a, b)),
                rtol=1e-4, atol=1e-4,
            )
            # Served through the session's registry dispatch, with the
            # inherited raw-tuple hot-path key (kind, K, N).
            assert ("doubled_gemm_toy", 32, 24) in eng._dispatch
            # The generic handle works for the toy kind too.
            op = vortex.ops.doubled_gemm_toy.handle_for(a, b)
            assert isinstance(op, CompiledOp)
            assert op.kind == "doubled_gemm_toy"
            assert op.bucket(13) == op.select(13).padded_m
    finally:
        WORKLOADS.pop("doubled_gemm_toy", None)
        vortex.ops._OPS.pop("doubled_gemm_toy", None)


def test_ops_unknown_kind_raises():
    with pytest.raises(AttributeError, match="no workload kind"):
        vortex.ops.definitely_not_registered


def test_ops_dir_lists_registry():
    listing = dir(vortex.ops)
    assert {"gemm", "attention", "conv2d"} <= set(listing)


def test_compile_by_kind_name_and_instance_agree():
    eng = _engine()
    by_name = eng.compile("gemm", M=None, N=24, K=32)
    by_inst = eng.compile(GemmWorkload(M=None, N=24, K=32))
    assert by_name.kernel is by_inst.kernel  # one kernel per signature
    a, b = _arr((7, 32)), _arr((32, 24))
    np.testing.assert_array_equal(
        np.asarray(by_name(a, b)), np.asarray(by_inst(a, b))
    )


def test_compile_rejects_params_with_instance():
    with pytest.raises(TypeError, match="kind name"):
        _engine().compile(GemmWorkload(M=None, N=8, K=8), N=16)


# ---------------------------------------------------------------------------
# Sessions: contextvar scoping
# ---------------------------------------------------------------------------


def test_use_nests_and_restores():
    e1, e2 = _engine(), _engine()
    assert vortex.installed_engine() is None
    with vortex.use(e1):
        assert vortex.installed_engine() is e1
        assert vortex.current_engine() is e1
        with vortex.use(e2):
            assert vortex.installed_engine() is e2
        assert vortex.installed_engine() is e1
    assert vortex.installed_engine() is None


def test_use_restores_on_exception():
    e1, e2 = _engine(), _engine()
    with vortex.use(e1):
        with pytest.raises(ValueError):
            with vortex.use(e2):
                assert vortex.installed_engine() is e2
                raise ValueError("boom")
        assert vortex.installed_engine() is e1
    assert vortex.installed_engine() is None


def test_thread_isolation():
    """Two threads with different engines must not observe each other, and
    a fresh thread starts with NO installed engine even while the spawning
    thread holds one."""
    e_main, e_thread = _engine(), _engine()
    seen: dict[str, object] = {}
    installed = threading.Event()
    checked = threading.Event()

    def worker():
        seen["at_start"] = vortex.installed_engine()
        with vortex.use(e_thread):
            seen["inside"] = vortex.installed_engine()
            installed.set()
            checked.wait(timeout=10)
        seen["after"] = vortex.installed_engine()

    with vortex.use(e_main):
        t = threading.Thread(target=worker)
        t.start()
        installed.wait(timeout=10)
        # The worker holds e_thread; this thread still sees e_main.
        assert vortex.installed_engine() is e_main
        checked.set()
        t.join(timeout=10)
    assert seen["at_start"] is None
    assert seen["inside"] is e_thread
    assert seen["after"] is None


def test_current_engine_falls_back_to_process_default():
    assert vortex.installed_engine() is None
    d1 = vortex.current_engine()
    d2 = vortex.current_engine()
    assert d1 is d2 is vortex.default_engine()
    with vortex.use(_engine()) as eng:
        assert vortex.current_engine() is eng


def test_engine_use_shorthand():
    eng = _engine()
    with eng.use():
        assert vortex.installed_engine() is eng
    assert vortex.installed_engine() is None


# ---------------------------------------------------------------------------
# EngineConfig
# ---------------------------------------------------------------------------


def test_engine_config_is_frozen_and_overridable():
    cfg = EngineConfig(hardware="tpu_v5e", backends=["mxu"])
    assert cfg.backends == ("mxu",)  # normalized to a tuple (hashable)
    hash(cfg)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.impl = "pallas"
    eng = Engine(cfg, empirical_levels=())
    assert eng.config.hardware == "tpu_v5e"
    assert eng.config.empirical_levels == ()


def test_engine_derives_hardware_and_impl_from_the_platform():
    eng = Engine(empirical_levels=())
    assert (eng.config.hardware, eng.config.impl) == ("host_cpu", "xla")
    assert Engine("tpu_v5e", empirical_levels=()).config.impl == "xla"
    with pytest.raises(ValueError, match="impl"):
        EngineConfig(impl="mosaic")


@pytest.mark.parametrize("platform,kind,expect", [
    ("cpu", "cpu", ("host_cpu", False)),
    ("tpu", "TPU v5 lite", ("tpu_v5e", True)),
    ("tpu", "TPU v4", None),
    ("gpu", "NVIDIA H100", None),
])
def test_resolve_platform_maps_known_kinds_only(platform, kind, expect):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    if expect is None:
        with pytest.raises(ValueError, match=kind):
            resolve_platform(dev)
    else:
        got = resolve_platform(dev)
        assert (got.hardware.name, got.native_pallas) == expect


def test_pallas_interprets_only_on_the_cpu(monkeypatch):
    assert interpret_pallas() is True
    assert interpret_pallas(False) is False
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    assert interpret_pallas() is False
    with pytest.raises(ValueError, match="interpret"):
        interpret_pallas(True)


def test_config_table_limits_reach_the_selector():
    eng = Engine(EngineConfig(
        hardware="host_cpu", empirical_levels=(), table_m_max=32,
        table_extend_limit=64,
    ))
    kern = eng.compile("gemm", M=None, N=16, K=16).kernel
    assert kern.selector.table.m_max == 32
    kern.select(1000)  # beyond the extension limit: table must not grow
    assert kern.selector.table.m_max == 32


def test_precompile_policy_warms_unspecialized_ops_only():
    eng = Engine(EngineConfig(
        hardware="host_cpu", empirical_levels=(), precompile_m_max=64
    ))
    gemm = eng.compile("gemm", M=None, N=16, K=16)
    expect = len(gemm.kernel.selector.selections_upto(64))
    assert gemm.stats()["exec"]["entries"] == expect > 0
    # Attention executables specialize on batch/head dims: eager precompile
    # without representative args would warm keys real calls never hit.
    attn = eng.compile("attention", seq=None, head_dim=32)
    assert attn.stats()["exec"]["entries"] == 0


# ---------------------------------------------------------------------------
# Precompile diagnostics (PrecompileError names the failing Selection)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("max_workers", [1, 4], ids=["serial", "parallel"])
def test_precompile_failure_names_selection(max_workers):
    op = _engine().compile("gemm", M=None, N=16, K=16)
    kern = op.kernel

    def broken(sel, args):
        raise RuntimeError("builder exploded")

    kern._build_executable = broken
    with pytest.raises(PrecompileError) as exc:
        op.precompile(64, max_workers=max_workers)
    msg = str(exc.value)
    assert "gemm" in msg and "bucket=" in msg and "backend=" in msg
    assert "builder exploded" in msg
    assert exc.value.selection.bucket[0] >= 1


# ---------------------------------------------------------------------------
# Deprecation shims: warn, delegate, and stay bit/key-identical
# ---------------------------------------------------------------------------


def test_vortex_engine_shim_parity_gemm():
    """VortexEngine.gemm must produce bit-identical outputs and identical
    dispatch/kernel/executable-cache keys to the registry-driven path."""
    from repro.core import VortexEngine

    a, b = _arr((13, 48)), _arr((48, 32))
    old = VortexEngine("host_cpu", empirical_levels=())
    new = _engine()
    with pytest.warns(VortexDeprecationWarning, match="VortexEngine.gemm"):
        y_old = old.gemm(a, b)
    y_new = new.dispatch("gemm", a, b)
    np.testing.assert_array_equal(np.asarray(y_old), np.asarray(y_new))
    assert set(old._dispatch) == set(new._dispatch) == {("gemm", 48, 32)}
    assert set(old._kernels) == set(new._kernels)
    k_old = next(iter(old._kernels.values()))
    k_new = next(iter(new._kernels.values()))
    assert set(k_old._exec_cache) == set(k_new._exec_cache)


def test_vortex_engine_shim_parity_attention_and_conv():
    from repro.core import VortexEngine

    old = VortexEngine("host_cpu", empirical_levels=())
    new = _engine()
    q, k, v = _arr((1, 4, 19, 32)), _arr((1, 2, 19, 32)), _arr((1, 2, 19, 32))
    with pytest.warns(VortexDeprecationWarning):
        y_old = old.attention(q, k, v, window=8)
    y_new = new.dispatch("attention", q, k, v, window=8)
    np.testing.assert_array_equal(np.asarray(y_old), np.asarray(y_new))

    x, w = _arr((2, 9, 9, 4)), _arr((3, 3, 4, 8))
    with pytest.warns(VortexDeprecationWarning):
        c_old = old.conv2d(x, w)
    c_new = new.dispatch("conv2d", x, w)
    np.testing.assert_array_equal(np.asarray(c_old), np.asarray(c_new))
    assert set(old._dispatch) == set(new._dispatch)
    assert set(old._kernels) == set(new._kernels)


def test_vortex_gemm_shim_warns_and_matches_kernel():
    from repro.core import VortexKernel, VortexGemm
    from repro.core.hardware import HOST_CPU

    wl = GemmWorkload(M=None, N=24, K=32)
    with pytest.warns(VortexDeprecationWarning, match="VortexGemm"):
        old = VortexGemm(HOST_CPU, wl, empirical_levels=())
    new = VortexKernel(HOST_CPU, wl, empirical_levels=())
    a, b = _arr((9, 32)), _arr((32, 24))
    np.testing.assert_array_equal(np.asarray(old(a, b)), np.asarray(new(a, b)))
    assert set(old._exec_cache) == set(new._exec_cache)
    assert old.select(9).bucket == new.select(9).bucket


def test_set_attention_engine_shim_delegates_to_contextvar():
    """The deprecated imperative surface must be a view over the SAME
    contextvar vortex.use writes."""
    from repro.models import layers

    eng = _engine()
    with pytest.warns(VortexDeprecationWarning, match="set_attention_engine"):
        prev = layers.set_attention_engine(eng)
    assert prev is None
    assert vortex.installed_engine() is eng  # same underlying session
    with pytest.warns(VortexDeprecationWarning, match="get_attention_engine"):
        assert layers.get_attention_engine() is eng
    with pytest.warns(VortexDeprecationWarning, match="set_attention_engine"):
        assert layers.set_attention_engine(None) is eng
    assert vortex.installed_engine() is None
    # And the other direction: a vortex.use install is visible through the
    # deprecated getter.
    with vortex.use(eng):
        with pytest.warns(VortexDeprecationWarning):
            assert layers.get_attention_engine() is eng


def test_attention_engine_contextmanager_shim():
    from repro.models import layers

    eng = _engine()
    with pytest.warns(VortexDeprecationWarning, match="attention_engine"):
        with layers.attention_engine(eng):
            assert vortex.installed_engine() is eng
    assert vortex.installed_engine() is None


def test_internal_deprecations_are_errors_by_default():
    """Tier-1 runs with repro's own DeprecationWarnings as errors (see
    pyproject filterwarnings): an un-caught shim call must raise, so
    internal callers cannot silently regress onto the old surface."""
    from repro.core import VortexEngine

    eng = VortexEngine("host_cpu", empirical_levels=())
    with pytest.raises(VortexDeprecationWarning):
        eng.gemm(_arr((4, 8)), _arr((8, 4)))


# ---------------------------------------------------------------------------
# CompiledOp handle surface
# ---------------------------------------------------------------------------


def test_compiled_op_call_select_bucket_stats():
    op = vortex.compile(
        GemmWorkload(M=None, N=32, K=48), engine=_engine()
    )
    a, b = _arr((21, 48)), _arr((48, 32))
    np.testing.assert_allclose(
        np.asarray(op(a, b)), np.asarray(ref_gemm(a, b)),
        rtol=1e-4, atol=1e-4,
    )
    sel = op.select(21)
    assert op.bucket(21) == sel.padded_m >= 21
    assert op.bucket(21) in op.buckets(64)
    n = op.precompile(64)
    assert n >= 1
    s = op.stats()
    assert s["kind"] == "gemm"
    assert s["select"]["selects"] >= 2
    assert s["exec"]["entries"] >= 1
    assert s["offline"].num_candidates > 0


def test_compiled_op_attention_with_representative_args():
    eng = _engine()
    op = eng.compile(AttentionWorkload(seq=None, head_dim=32))
    q, k, v = _arr((2, 4, 5, 32)), _arr((2, 2, 5, 32)), _arr((2, 2, 5, 32))
    op.precompile(64, q, k, v)
    entries = op.stats()["exec"]["entries"]
    assert entries >= 1
    with vortex.use(eng):
        out = vortex.ops.attention(
            q, k, v
        )  # same signature: served from the warmed cache
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_attention(q, k, v, causal=True)),
        rtol=1e-4, atol=1e-4,
    )
    assert op.stats()["exec"]["entries"] == entries  # no new compiles
