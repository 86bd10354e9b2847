"""Partitioning rules, spec sanitization, and the roofline HLO parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.config import SHAPES
from repro.models import model as M
from repro.models.params import abstract_params, param_pspecs
from repro.models.partitioning import AxisRules, make_rules
from repro.models.registry import get_config
from repro.roofline.hlo_parse import parse_hlo_costs
from repro.roofline.memory import tree_device_bytes
from repro.train.step import serve_input_specs, train_input_specs


def _abstract_rules(shape=(16, 16), axes=("data", "model"),
                    fsdp=False, n_heads=16, n_kv_heads=8):
    mesh = jax.sharding.AbstractMesh(shape, axes)
    return make_rules(
        mesh, fsdp=fsdp, n_heads=n_heads, n_kv_heads=n_kv_heads
    )


class TestRules:
    def test_sanitize_drops_non_divisible(self):
        r = _abstract_rules()
        assert r.sanitize(P("model"), (49155,)) == P()
        assert r.sanitize(P("model"), (49152,)) == P("model")
        assert r.sanitize(P(("pod", "data")), (1,)) == P()

    def test_heads_act_requires_divisibility(self):
        r = _abstract_rules(n_heads=24)  # 24 % 16 != 0
        assert r.rules["heads_act"] is None
        r2 = _abstract_rules(n_heads=32)
        assert r2.rules["heads_act"] == "model"

    def test_fsdp_maps_embed_to_data(self):
        r = _abstract_rules(fsdp=True)
        assert r.rules["embed"] == "data"
        r2 = _abstract_rules(fsdp=False)
        assert r2.rules["embed"] is None

    def test_multipod_batch_spans_pod_and_data(self):
        r = _abstract_rules(
            shape=(2, 16, 16), axes=("pod", "data", "model")
        )
        assert r.rules["batch"] == ("pod", "data")


class TestSpecTrees:
    @pytest.mark.parametrize("arch", ["gemma2-9b", "deepseek-v2-236b",
                                      "falcon-mamba-7b", "whisper-small"])
    def test_param_specs_cover_every_leaf_and_divide(self, arch):
        cfg = get_config(arch)
        r = _abstract_rules(
            fsdp=cfg.fsdp, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads
        )
        params = abstract_params(cfg)
        specs = param_pspecs(cfg, r)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            for i, part in enumerate(tuple(spec)):
                if part is None:
                    continue
                ext = r._extent(part)
                assert leaf.shape[i] % ext == 0, (leaf.shape, spec)

    def test_big_models_fit_hbm_under_sharding(self):
        """The FSDP+TP layout puts deepseek-v2 params well under 16 GB/chip."""
        cfg = get_config("deepseek-v2-236b")
        r = _abstract_rules(fsdp=True, n_heads=128, n_kv_heads=128)
        params = abstract_params(cfg)
        specs = param_pspecs(cfg, r)
        nbytes = tree_device_bytes(
            params, specs, {"data": 16, "model": 16}
        )
        assert nbytes < 4 * 2**30  # params alone < 4 GiB/chip

    def test_cache_specs_match_cache_tree(self):
        cfg = get_config("jamba-v0.1-52b")
        r = _abstract_rules(n_heads=32, n_kv_heads=8)
        cache = M.abstract_cache(cfg, batch=128, cache_len=1024)
        specs = M.cache_pspecs(cfg, r, batch=128, cache_len=1024)
        # encoder_out absent; same tree structure otherwise
        assert set(cache) == set(specs)
        jax.tree.map(
            lambda c, s: None, cache, specs,
            is_leaf=lambda x: isinstance(x, (P, jax.ShapeDtypeStruct)),
        )

    def test_input_specs_all_cells(self):
        """Every assigned (arch x shape) produces well-formed input specs."""
        from repro.models.registry import ARCH_IDS

        r = _abstract_rules()
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in SHAPES.values():
                if shape.kind == "train":
                    specs, ps = train_input_specs(cfg, shape, r)
                else:
                    specs, ps = serve_input_specs(cfg, shape, r)
                assert "tokens" in specs and "tokens" in ps


class TestHloParser:
    def test_scan_trip_count_correction(self):
        def f(x):
            def body(c, _):
                return c @ c, None
            c, _ = jax.lax.scan(body, x, None, length=7)
            return c

        compiled = jax.jit(f).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32)
        ).compile()
        costs = parse_hlo_costs(compiled.as_text())
        assert costs.flops == pytest.approx(7 * 2 * 64**3, rel=0.01)
        assert 7 in costs.while_trip_counts.values()

    def test_plain_dot_flops(self):
        compiled = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((32, 48), jnp.float32),
            jax.ShapeDtypeStruct((48, 16), jnp.float32),
        ).compile()
        costs = parse_hlo_costs(compiled.as_text())
        assert costs.flops == pytest.approx(2 * 32 * 48 * 16, rel=0.01)

    def test_collectives_counted_with_bytes(self):
        """An explicitly sharded reduction must show an all-reduce (or
        reduce-scatter) with nonzero bytes."""
        from jax.sharding import NamedSharding

        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs >1 device for a real collective")

    def test_memory_bytes_positive(self):
        compiled = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((32, 48), jnp.float32),
            jax.ShapeDtypeStruct((48, 16), jnp.float32),
        ).compile()
        costs = parse_hlo_costs(compiled.as_text())
        expect = 4 * (32 * 48 + 48 * 16 + 32 * 16)
        assert costs.memory_bytes >= expect
