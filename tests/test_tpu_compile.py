"""The main path's Pallas kernels compile for a v5e chip at real widths.

Interpret mode checks neither VMEM nor tiling, so these tests hand the
kernels to the TPU compiler for a described (unattached) ``v5e:2x2``
topology: shapes only, nothing runs.  The tiles are the ones the
``tpu_v5e`` lattice selects for ``paper-gpt2-124m`` (GEMMs, prefill and
decode attention), for ``phi4-mini-3.8b``'s decode attention and for a
``granite-moe-1b-a400m`` expert FFN (grouped GEMM), at the VMEM limit the
engine passes.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file.  Where it cannot be described, the compile tests skip.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.configs.granite_moe_1b import CONFIG as GRANITE
from repro.core.workloads import (
    AttentionWorkload,
    DecodeAttentionWorkload,
    GemmWorkload,
    GroupedGemmWorkload,
)
from repro.kernels.attention import (
    decode_grid_steps,
    decode_vmem_bytes,
    flash_attention,
)
from repro.kernels.gemm import vortex_gemm
from repro.kernels.grouped_gemm import vortex_grouped_gemm
from repro.models.config import SHAPES
from repro.models.registry import ARCH_IDS, cell_supported, get_config
from repro.vortex import Engine, EngineConfig

GPT2 = get_config("paper-gpt2-124m")
D, HD, H = GPT2.d_model, GPT2.resolved_head_dim, GPT2.n_heads
FFN_UP = (D, GPT2.d_ff)
LM_HEAD = (D, GPT2.vocab_padded)
EXPERT_UP = (GRANITE.d_model, GRANITE.moe.d_ff_expert)
EXPERT_DOWN = (GRANITE.moe.d_ff_expert, GRANITE.d_model)
E = GRANITE.moe.num_experts


def gemm_sigs(cfg) -> list[tuple[int, int]]:
    """Every (K, N) GEMM signature of ``cfg``'s attention-plus-dense
    block and its LM head: q/k/v/o projections, the MLP pair, the head."""
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


@pytest.fixture(scope="module")
def engine():
    return Engine(EngineConfig(
        hardware="tpu_v5e", backends=("mxu",), impl="pallas",
        empirical_levels=(), denylist_persist=False,
    ))


@pytest.fixture(scope="module")
def served_engine():
    """The engine ``VortexServer`` builds on a v5e: its decode attention
    selects the kv block the served decode programs run."""
    return Engine(EngineConfig(
        hardware="tpu_v5e", backends=("mxu",), impl="pallas",
    ))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for one described chip from (shape, dtype) pairs and
    return the compiled HLO text (raises what the chip's compiler raises)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [16, 1024, 2048, 8192])
@pytest.mark.parametrize("sig", [FFN_UP, LM_HEAD], ids=["ffn_up", "lm_head"])
def test_selected_gemm_tile_compiles(engine, one_chip, sig, m):
    k, n = sig
    kern = engine.kernel_for(GemmWorkload(M=None, N=n, K=k))
    sel = kern.select(m)
    bm, bn, bk = sel.strategy.l1

    def fn(a, b, m_true):
        return vortex_gemm(
            a, b, m_true, block_m=bm, block_n=bn, block_k=bk,
            interpret=False, vmem_limit_bytes=kern.vmem_limit_bytes,
        )

    hlo = _compile(
        one_chip, fn, ((sel.padded_m, k), jnp.bfloat16),
        ((k, n), jnp.bfloat16), ((), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


def test_prefill_attention_compiles_at_full_context(engine, one_chip):
    kern = engine.kernel_for(AttentionWorkload(seq=None, head_dim=HD))
    sel = kern.select(1024)
    pq, _, pkv = sel.bucket
    bq, _, bk = sel.strategy.l1

    def fn(q, k, v, kv_len):
        return flash_attention(
            q, k, v, kv_len, block_q=bq, block_k=bk, causal=True,
            interpret=False, vmem_limit_bytes=kern.vmem_limit_bytes,
        )

    hlo = _compile(
        one_chip, fn, ((1, H, pq, HD), jnp.bfloat16),
        ((1, H, pkv, HD), jnp.bfloat16), ((1, H, pkv, HD), jnp.bfloat16),
        ((), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles_with_per_row_kv_len(engine, one_chip):
    kern = engine.kernel_for(
        DecodeAttentionWorkload(seq=None, head_dim=HD)
    )
    sel = kern.select(1024)
    kvb, bk = sel.bucket[2], sel.strategy.l1[2]
    b = 8

    def fn(q, k, v, kv_len):
        return flash_attention(
            q, k, v, kv_len, q_offset=kv_len - 1, block_q=1, block_k=bk,
            causal=False, interpret=False,
            vmem_limit_bytes=kern.vmem_limit_bytes,
        )

    hlo = _compile(
        one_chip, fn, ((b, H, 1, HD), jnp.bfloat16),
        ((b, H, kvb, HD), jnp.bfloat16), ((b, H, kvb, HD), jnp.bfloat16),
        ((b,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "widths",
    [(8, 3, 128, 2048, 8), (12, 1, 64, 1024, 64)],
    ids=["phi4", "gpt2"],
)
def test_decode_grid_compiles_at_served_widths(
    served_engine, one_chip, widths
):
    """The decode grid at the served cells' widths, cache buckets and
    rows: one step per (row, kv block) with every KV head in the block,
    compiled for one v5e at the kv block the server selects."""
    hkv, group, hd, kvb, b = widths
    kern = served_engine.kernel_for(
        DecodeAttentionWorkload(seq=None, head_dim=hd)
    )
    sel = kern.select(kvb)
    assert sel.bucket[2] == kvb
    bk = sel.strategy.l1[2]

    def fn(q, k, v, kv_len):
        return flash_attention(
            q, k, v, kv_len, q_offset=kv_len - 1, block_q=1, block_k=bk,
            causal=False, interpret=False,
            vmem_limit_bytes=kern.vmem_limit_bytes,
        )

    shapes = (
        ((b, hkv * group, 1, hd), jnp.bfloat16),
        ((b, hkv, kvb, hd), jnp.bfloat16), ((b, hkv, kvb, hd), jnp.bfloat16),
        ((b,), jnp.int32),
    )
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    steps = decode_grid_steps(jax.make_jaxpr(fn)(*args).jaxpr)
    assert steps == b * (kvb // bk)
    assert "tpu_custom_call" in _compile(one_chip, fn, *shapes)


def test_decode_grid_streams_kv_from_hbm(one_chip):
    """K and V reach the decode kernel in HBM.  A layer sliced out of a
    stacked cache at phi4-batch's widths (32 MiB) fits the VMEM the
    kernel leaves over, and the compiler would otherwise stage it whole
    in VMEM inside the slice, outside the kernel."""
    b, hkv, group, hd, kvb = 8, 8, 3, 128, 2048

    def fn(q, k_stack, v_stack, layer, kv_len):
        k, v = (
            jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
            for x in (k_stack, v_stack)
        )
        return flash_attention(
            q, k, v, kv_len, q_offset=kv_len - 1, block_q=1, block_k=512,
            causal=False, interpret=False, vmem_limit_bytes=96 * 2**20,
        )

    hlo = _compile(
        one_chip, fn, ((b, hkv * group, 1, hd), jnp.bfloat16),
        ((4, b, hkv, kvb, hd), jnp.bfloat16),
        ((4, b, hkv, kvb, hd), jnp.bfloat16),
        ((), jnp.int32), ((b,), jnp.int32),
    )
    (call,) = re.findall(
        r"%flash_attention\S* = .*? custom-call\(([^)]*)\)", hlo
    )
    kv_operands = [o.strip().lstrip("%") for o in call.split(",")][2:]
    for name in kv_operands:
        (shape,) = re.findall(rf"%{re.escape(name)} = (\S+) ", hlo)
        assert str(kvb) in shape and "S(1)" not in shape, (name, shape)


SERVED_ATTN = [
    a for a in ("paper-gpt2-124m", *ARCH_IDS)
    if any(spec.mixer == "attn" for spec in get_config(a).pattern)
]


@pytest.mark.parametrize("arch", SERVED_ATTN)
def test_decode_row_block_fits_the_vmem_limit(served_engine, arch):
    """Every registered configuration's decode block, all of a row's KV
    heads at the selected kv block, fits the VMEM limit the engine passes
    at every kv bucket it decodes at: to the registry's 32k decode shape,
    and to 512k where the configuration serves that shape."""
    cfg = get_config(arch)
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    group = cfg.n_heads // hkv
    kern = served_engine.kernel_for(
        DecodeAttentionWorkload(seq=None, head_dim=hd)
    )
    longest = SHAPES[
        "long_500k" if cell_supported(arch, "long_500k")[0] else "decode_32k"
    ].seq_len
    kv = 1024
    while kv <= longest:
        bk = kern.select(kv).strategy.l1[2]
        need = decode_vmem_bytes(hkv, group, bk, hd, 2)
        assert need <= kern.vmem_limit_bytes, (kv, bk, need)
        kv *= 2


@pytest.mark.parametrize(
    "sig", [EXPERT_UP, EXPERT_DOWN], ids=["expert_up", "expert_down"]
)
def test_grouped_gemm_compiles_at_granite_widths(engine, one_chip, sig):
    k, n = sig
    kern = engine.kernel_for(
        GroupedGemmWorkload(C=None, G=E, E=E, N=n, K=k)
    )
    sel = kern.select(200)  # capacity rows per expert
    bm, bn, bk = sel.strategy.l1

    def fn(x, w, counts):
        return vortex_grouped_gemm(
            x, w, counts, block_m=bm, block_n=bn, block_k=bk,
            interpret=False, vmem_limit_bytes=kern.vmem_limit_bytes,
        )

    hlo = _compile(
        one_chip, fn, ((E, sel.padded_m, k), jnp.bfloat16),
        ((E, k, n), jnp.bfloat16), ((E,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


class _KernelSession:
    """Stands in for the serving session while a decode program is traced:
    decode attention becomes the Pallas kernel compiled for the chip at
    the lattice's selection, as ``DecodeAttentionWorkload`` builds it."""

    def __init__(self, engine):
        self.engine = engine

    def dispatch(self, kind, q, k, v, kv_len, *, window=None, softcap=None):
        assert kind == "decode_attention"
        kern = self.engine.kernel_for(
            DecodeAttentionWorkload(seq=None, head_dim=q.shape[-1])
        )
        sel = kern.select(k.shape[-2])
        return flash_attention(
            q, k, v, kv_len, q_offset=kv_len - 1, block_q=1,
            block_k=sel.strategy.l1[2], causal=False, window=window,
            softcap=softcap, interpret=False,
            vmem_limit_bytes=kern.vmem_limit_bytes,
        )


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
def test_decode_program_updates_cache_in_place(
    engine, topo, per_row, monkeypatch
):
    """The whole donated decode program at full width, with the Pallas
    decode kernel, for one described chip: every cache leaf is aliased to
    the output, and no whole stacked leaf is copied (into another layout
    or otherwise), broadcast or transposed."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.models.model import abstract_cache
    from repro.models.params import init_params
    from repro.models.partitioning import make_rules
    from repro.train.step import make_decode_step
    from repro.vortex import session

    monkeypatch.setattr(
        session, "installed_engine", lambda: _KernelSession(engine)
    )
    mesh = Mesh(
        np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model")
    )
    on_chip = NamedSharding(mesh, PartitionSpec())
    rules = make_rules(mesh, n_heads=GPT2.n_heads, n_kv_heads=GPT2.n_kv_heads)
    kvb = engine.kernel_for(
        DecodeAttentionWorkload(seq=None, head_dim=HD)
    ).select(1024).bucket[2]
    b = 8

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
            tree,
        )

    params = jax.eval_shape(lambda: init_params(GPT2, jax.random.PRNGKey(0)))
    cache = abstract_cache(GPT2, b, kvb)
    step = jax.jit(
        make_decode_step(GPT2, rules, cache_len=kvb), donate_argnums=(1,)
    )
    hlo = step.lower(
        shapes(params), shapes(cache),
        jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=on_chip),
        jax.ShapeDtypeStruct((b,) if per_row else (), jnp.int32,
                             sharding=on_chip),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    header = hlo.splitlines()[0]
    n_params = len(jax.tree.leaves(params))
    n_cache = len(jax.tree.leaves(cache))
    aliased = {
        int(p) for p in re.findall(r"\}: \((\d+), \{\}, may-alias\)", header)
    }
    assert aliased == set(range(n_params, n_params + n_cache)), header
    leaf = re.escape("[" + ",".join(map(str, cache["pos0"]["k"].shape)) + "]")
    whole = [
        (name, op)
        for name, op in re.findall(rf"%(\S+) = \w+{leaf}\S* ([\w-]+)\(", hlo)
        if op in ("copy", "broadcast", "transpose", "custom-call")
        or (op == "fusion"
            and any(w in name for w in ("copy", "broadcast", "transpose")))
    ]
    assert not whole, whole
    # The new token's rows are written, not selected into a whole slice.
    assert "broadcast_select_fusion" not in hlo


def _pallas_calls(jaxpr):
    """Every pallas_call equation in ``jaxpr``, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None:
                out += _pallas_calls(getattr(inner, "jaxpr", inner))
    return out


def _vmem_allocated(eqn, dtype_bytes):
    """VMEM bytes a pallas_call's own specs allocate: two buffers of each
    VMEM block (in the workload's element size) plus the scratch."""
    gm = eqn.params["grid_mapping"]
    blocks = sum(
        math.prod(bm.block_aval.inner_aval.shape)
        for bm in gm.block_mappings
        if bm.block_aval.memory_space != pltpu.SMEM
    )
    scratch = sum(
        math.prod(a.inner_aval.shape) * a.inner_aval.dtype.itemsize
        for a in gm.scratch_avals
    )
    return 2 * blocks * dtype_bytes + scratch


def test_selectable_tiles_fit_the_vmem_limit_passed_to_the_compiler(engine):
    """For the model's GEMM and attention signatures: every lattice tile
    counts no more than the VMEM limit; the count covers what the kernel's
    specs allocate for the tiles the selector picks; and the built
    executable hands that limit to the compiler."""
    gemms = [GemmWorkload(M=None, N=n, K=k) for k, n in gemm_sigs(GPT2)]
    grouped = [
        GroupedGemmWorkload(C=None, G=E, E=E, N=n, K=k)
        for k, n in (EXPERT_UP, EXPERT_DOWN)
    ]
    attn = [AttentionWorkload(seq=None, head_dim=HD),
            DecodeAttentionWorkload(seq=None, head_dim=HD)]
    for wl in gemms + grouped + attn:
        kern = engine.kernel_for(wl)
        limit = kern.vmem_limit_bytes
        for scored in kern.selector.scored.values():
            for tile in scored.l1_tiles:
                assert wl.l1_tile_bytes(tuple(tile)) <= limit, (wl, tile)
        for m in (1, 300, 1024):
            sel = kern.select(m)
            fn = wl.build_executable(
                sel, impl="pallas", vmem_limit_bytes=limit
            )
            args = [
                jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a))
                for a in wl.example_args(sel)
            ]
            (eqn,) = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
            mosaic = eqn.params["compiler_params"]["mosaic_tpu"]
            assert mosaic.vmem_limit_bytes == limit, wl
            counted = wl.l1_tile_bytes(sel.strategy.l1)
            assert _vmem_allocated(eqn, wl.dtype_bytes) <= counted, (wl, m)
