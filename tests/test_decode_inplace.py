"""The decode programs update the shared KV cache in place.

Both compiled decode programs (scalar ``pos`` for ``generate()``, per-row
``pos`` for the scheduler) donate the cache: every cache leaf is aliased
to its output, the donated input is deleted by the call, and no whole
stacked leaf is copied, broadcast or transposed inside the program — only
the new token's rows are written.  A program that consumes the cache
and then raises must leave the server and scheduler serviceable: leases
settle, the pool never parks or hands out a deleted buffer, and the next
request is served token-identical to the serial path.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.launch.scheduler import ContinuousScheduler
from repro.launch.serve import Request, RequestError, VortexServer
from repro.models.model import make_cache
from repro.models.registry import get_smoke_config

MAX_CACHE = 64
BP = 4

PROGRAMS = {
    "scalar": ("_decode_exec_for", ()),
    "per_row": ("_decode_exec_vec_for", (BP,)),
}


@pytest.fixture(scope="module")
def server():
    cfg = get_smoke_config("paper-gpt2-124m")
    return VortexServer(cfg, make_host_mesh(), max_cache=MAX_CACHE)


def _program(server, kind):
    name, _ = PROGRAMS[kind]
    return getattr(server, name)(BP, MAX_CACHE)


def _launch(server, kind, exe, cache):
    pos = jnp.full(PROGRAMS[kind][1], 3, jnp.int32)
    tok = jnp.ones((BP, 1), jnp.int32)
    return exe(server.params, cache, tok, pos)


@pytest.mark.parametrize("kind", PROGRAMS)
def test_program_aliases_every_cache_leaf(server, kind):
    hlo = _program(server, kind).as_text()
    header = hlo.splitlines()[0]
    aliased = {
        int(p) for p in re.findall(r"\}: \((\d+), \{\}, may-alias\)", header)
    }
    n_params = len(jax.tree.leaves(server.params))
    n_cache = len(jax.tree.leaves(make_cache(server.cfg, BP, MAX_CACHE)))
    assert aliased == set(range(n_params, n_params + n_cache)), header


WHOLE_LEAF_OPS = ("copy", "broadcast", "transpose")


@pytest.mark.parametrize("kind", PROGRAMS)
def test_program_never_copies_a_whole_leaf(server, kind):
    """No instruction (or fusion named for one) copies, broadcasts or
    transposes a whole stacked leaf: no restacked output buffer."""
    hlo = _program(server, kind).as_text()
    leaf = make_cache(server.cfg, BP, MAX_CACHE)["pos0"]["k"]
    shape = re.escape("[" + ",".join(map(str, leaf.shape)) + "]")
    whole = [
        (name, op)
        for name, op in re.findall(rf"%(\S+) = \w+{shape}\S* ([\w-]+)\(", hlo)
        if op in WHOLE_LEAF_OPS
        or (op == "fusion" and any(w in name for w in WHOLE_LEAF_OPS))
    ]
    assert not whole, whole


@pytest.mark.parametrize("kind", PROGRAMS)
def test_donated_cache_is_deleted_and_counted(server, kind):
    before = dict(server.stats)
    exe = _program(server, kind)
    cache = make_cache(server.cfg, BP, MAX_CACHE)
    logits, new = _launch(server, kind, exe, cache)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(cache))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(new))
    assert logits.shape[0] == BP
    assert server.stats["decode_inplace_launches"] == (
        before["decode_inplace_launches"] + 1
    )
    assert server.stats["decode_restack_launches"] == (
        before["decode_restack_launches"]
    )


def test_restack_path_counted_for_state_space_model():
    """A mamba decoder's state update is not a token-row write: its decode
    launches are counted on the restack path."""
    cfg = get_smoke_config("falcon-mamba-7b")
    srv = VortexServer(cfg, make_host_mesh(), max_cache=32)
    rng = np.random.default_rng(0)
    out = srv.generate(Request(
        tokens=rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
        max_new=4,
    ))
    assert out.shape == (1, 4)
    assert srv.stats["decode_restack_launches"] == 3
    assert srv.stats["decode_inplace_launches"] == 0
    assert srv.kv_pool.stats()["leases_active"] == 0


def _raise_after_first_launch(real):
    """Wrap a program getter: the first program handed out runs (consuming
    its donated cache) and then raises; later ones are the real ones."""
    state = {"raised": False}

    def getter(bp, kvb):
        exe = real(bp, kvb)
        if state["raised"]:
            return exe

        def launch(*args):
            exe(*args)
            state["raised"] = True
            raise RuntimeError("injected failure after the launch")

        return launch

    return getter


def _leases_checked(monkeypatch, pool):
    """Make every lease assert that it hands out a live buffer."""
    real = pool.lease

    def lease(*a, **kw):
        buf = real(*a, **kw)
        assert not buf.is_deleted()
        return buf

    monkeypatch.setattr(pool, "lease", lease)


def _parked_deleted(pool):
    return [b for bufs in pool._free.values() for b in bufs if b.is_deleted()]


@pytest.mark.parametrize("kind", PROGRAMS)
def test_launch_that_raises_after_consuming_cache_is_survivable(
    server, kind, monkeypatch
):
    """The scalar program fails a ``generate()``; the per-row program
    fails the scheduler's rows.  Either way the leases settle, nothing
    deleted is parked or leased, and the next request is served
    token-identical to serial ``generate()``."""
    rng = np.random.default_rng(11)
    reqs = [
        Request(tokens=rng.integers(0, 512, (1, int(s))).astype(np.int32),
                max_new=6)
        for s in (9, 17)
    ]
    serial = [server.generate(r) for r in reqs]
    idle = server.kv_pool.stats()["leases_active"]
    _leases_checked(monkeypatch, server.kv_pool)
    name, _ = PROGRAMS[kind]
    monkeypatch.setattr(
        server, name, _raise_after_first_launch(getattr(server, name))
    )

    if kind == "scalar":
        with pytest.raises(RuntimeError, match="injected"):
            server.generate(reqs[0])
        assert server.kv_pool.stats()["leases_active"] == idle
        assert not _parked_deleted(server.kv_pool)
        again = server.generate(reqs[1])
        assert np.array_equal(again, serial[1])
    else:
        sched = ContinuousScheduler(server, batch_rows=BP)
        rid = sched.submit(reqs[0])
        res = sched.drain()
        assert isinstance(res[rid], RequestError)
        assert res[rid].stage == "decode"
        assert sched.cache is None  # consumed, dropped, leases settled
        assert server.kv_pool.stats()["leases_active"] == idle
        assert not _parked_deleted(server.kv_pool)
        rid = sched.submit(reqs[1])
        res = sched.drain()
        assert np.array_equal(res[rid], serial[1])
        sched.close()
    assert server.kv_pool.stats()["leases_active"] == idle
    assert not _parked_deleted(server.kv_pool)
