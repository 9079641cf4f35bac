"""ucc_tpu.ops — traceable collectives inside user shard_map/jit programs
(the TPU-native triggered-post execution model, reference ucc.h:2050-2260)."""
import numpy as np
import pytest

from ucc_tpu.constants import ReductionOp
from ucc_tpu import ops

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.make_mesh((8,), ("r",))


def run_sm(mesh, fn, x, out_specs=P("r", None)):
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=P("r", None),
                            out_specs=out_specs, check_vma=False)
    return jax.jit(wrapped)(x)


class TestOpsInJit:
    def test_allreduce_sum(self, mesh):
        x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
        out = np.asarray(jax.device_get(
            run_sm(mesh, lambda v: ops.allreduce(v, ReductionOp.SUM), x)))
        expect = np.sum(np.asarray(x), axis=0)
        for r in range(8):
            np.testing.assert_allclose(out[r], expect)

    def test_allreduce_ring_matches_psum(self, mesh):
        x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
        ring = run_sm(mesh, lambda v: ops.allreduce_ring(v, ReductionOp.SUM), x)
        psum = run_sm(mesh, lambda v: ops.allreduce(v, ReductionOp.SUM), x)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(psum),
                                   rtol=1e-5)

    def test_reduce_scatter(self, mesh):
        x = jnp.ones((8, 16), jnp.float32)
        out = run_sm(mesh, lambda v: ops.reduce_scatter(v, ReductionOp.SUM), x)
        assert out.shape == (8, 2)
        np.testing.assert_allclose(np.asarray(out), 8.0)

    @pytest.mark.parametrize("order", [list(range(8)),
                                       [0, 1, 3, 5, 7, 6, 4, 2]])
    def test_reduce_scatter_ring_matches_psum_scatter(self, mesh, order):
        """The ring on 8 ranks, in rank order and in a snake's order, at
        an odd block (halves of 5 and 4): rank r gets block r."""
        x = jax.random.normal(jax.random.key(8), (8 * 8 * 9,), jnp.float32)

        def flat(fn):
            return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("r"),
                                         out_specs=P("r"),
                                         check_vma=False))(x)
        ring = flat(lambda v: ops.reduce_scatter_ring(
            v, ReductionOp.AVG, order))
        want = flat(lambda v: ops.reduce_scatter(v, ReductionOp.AVG))
        assert ring.shape == (8 * 9,)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_allgather(self, mesh):
        x = jnp.arange(8 * 2, dtype=jnp.int32).reshape(8, 2)
        out = np.asarray(jax.device_get(run_sm(mesh, ops.allgather, x)))
        assert out.shape == (8, 16)
        for r in range(8):
            np.testing.assert_array_equal(out[r], np.arange(16))

    def test_alltoall(self, mesh):
        n, blk = 8, 2
        x = jnp.arange(n * n * blk, dtype=jnp.int32).reshape(n, n * blk)
        out = np.asarray(jax.device_get(run_sm(mesh, ops.alltoall, x)))
        xin = np.asarray(x)
        for r in range(n):
            expect = np.concatenate(
                [xin[p, r * blk:(r + 1) * blk] for p in range(n)])
            np.testing.assert_array_equal(out[r], expect)

    def test_bcast(self, mesh):
        x = jnp.stack([jnp.full(4, float(r + 1)) for r in range(8)])
        out = run_sm(mesh, lambda v: ops.bcast(v, root=3), x)
        np.testing.assert_allclose(np.asarray(out), 4.0)

    def test_minloc(self, mesh):
        vals = np.random.default_rng(0).random((8, 6)).astype(np.float32)
        pairs = np.empty((8, 12), np.float32)
        pairs[:, 0::2] = vals
        pairs[:, 1::2] = np.arange(8)[:, None]
        out = np.asarray(jax.device_get(
            run_sm(mesh, lambda v: ops.allreduce(v, ReductionOp.MINLOC),
                   jnp.asarray(pairs))))
        np.testing.assert_allclose(out[0][0::2], vals.min(axis=0))
        np.testing.assert_array_equal(out[0][1::2].astype(np.int64),
                                      vals.argmin(axis=0))

    def test_composes_with_grad(self, mesh):
        """ops inside a differentiated program — the data-parallel
        gradient-sync use case (psum is linear, grad flows)."""
        def loss(w, x):
            def shard_fn(w, x):
                local = jnp.sum((x @ w) ** 2, keepdims=True)[None]
                return ops.allreduce(local, ReductionOp.SUM)
            f = jax.shard_map(shard_fn, mesh=mesh,
                              in_specs=(P(), P("r", None)),
                              out_specs=P(None, None), check_vma=False)
            return f(w, x)[0, 0]

        w = jnp.ones((4,), jnp.float32)
        x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4) / 10
        g = jax.jit(jax.grad(loss))(w, x)
        assert g.shape == (4,) and bool(jnp.all(jnp.isfinite(g)))


class TestOpsAlltoallv:
    """In-jit alltoallv with a static counts matrix (packed layout)."""

    @pytest.mark.parametrize("seed", [0, 9])
    def test_matches_numpy(self, seed):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = min(8, len(jax.devices()))
        if n < 2:
            pytest.skip("needs >= 2 devices")
        rng = np.random.default_rng(seed)
        m = rng.integers(0, 5, size=(n, n))
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("r",))
        max_src = max(1, int(m.sum(axis=1).max()))
        max_dst = max(1, int(m.sum(axis=0).max()))
        srcs = []
        for i in range(n):
            tot = int(m[i].sum())
            s = np.zeros(max_src, np.float32)
            s[:tot] = np.arange(tot) + 100 * i
            srcs.append(s)
        garr = jax.make_array_from_single_device_arrays(
            (n * max_src,), NamedSharding(mesh, P("r")),
            [jax.device_put(jnp.asarray(srcs[i]), mesh.devices.reshape(-1)[i])
             for i in range(n)])

        prog = jax.jit(jax.shard_map(
            lambda x: ops.alltoallv(x, m), mesh=mesh, in_specs=P("r"),
            out_specs=P("r"), check_vma=False))
        out = prog(garr)
        shards = {s.device: np.asarray(s.data)
                  for s in out.addressable_shards}
        devs = mesh.devices.reshape(-1)
        for i in range(n):
            got = shards[devs[i]]
            off = 0
            for p in range(n):
                c = int(m[p, i])
                sd = int(np.sum(m[p, :i]))
                expect = (np.arange(int(m[p].sum())) + 100 * p)[sd:sd + c]
                np.testing.assert_array_equal(got[off:off + c], expect)
                off += c
            np.testing.assert_array_equal(got[off:max_dst], 0)

    def test_allgatherv_matches_numpy(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = min(8, len(jax.devices()))
        if n < 2:
            pytest.skip("needs >= 2 devices")
        counts = [(i % 4) for i in range(n)]      # includes zeros
        maxc = max(1, max(counts))
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("r",))
        srcs = []
        for i in range(n):
            s = np.zeros(maxc, np.int32)
            s[:counts[i]] = np.arange(counts[i]) + 10 * i
            srcs.append(s)
        garr = jax.make_array_from_single_device_arrays(
            (n * maxc,), NamedSharding(mesh, P("r")),
            [jax.device_put(jnp.asarray(srcs[i]),
                            mesh.devices.reshape(-1)[i])
             for i in range(n)])
        prog = jax.jit(jax.shard_map(
            lambda x: ops.allgatherv(x, counts), mesh=mesh,
            in_specs=P("r"), out_specs=P(None), check_vma=False))
        out = np.asarray(prog(garr))
        expect = np.concatenate(
            [np.arange(counts[i], dtype=np.int32) + 10 * i
             for i in range(n)])
        np.testing.assert_array_equal(out, expect)


class _Dev:
    """A device as ``ops.ring_order`` sees it: its coordinates or none."""

    def __init__(self, coords=None):
        self.coords = coords


class TestRingOrder:
    """The physical ring that ``reduce_scatter_ring`` travels."""

    @staticmethod
    def _hops_are_neighbours(devs, order):
        for a, b in zip(order, order[1:] + order[:1]):
            ca, cb = devs[a].coords, devs[b].coords
            assert sum(abs(p - q) for p, q in zip(ca, cb)) == 1, (a, b)

    def test_v5e_2x2(self):
        # device ids 0-3 of a v5e 2x2 sit at (0,0), (1,0), (0,1), (1,1)
        devs = [_Dev([x, y, 0]) for y in range(2) for x in range(2)]
        assert ops.ring_order(devs) == [0, 1, 3, 2]

    @pytest.mark.parametrize("xs,ys", [(2, 4), (4, 2), (3, 4), (4, 4)])
    def test_snake_over_a_grid(self, xs, ys):
        devs = [_Dev([x, y, 0]) for y in range(ys) for x in range(xs)]
        order = ops.ring_order(devs)
        assert sorted(order) == list(range(xs * ys)) and order[0] == 0
        self._hops_are_neighbours(devs, order)

    @pytest.mark.parametrize("devs", [
        [_Dev() for _ in range(4)],                       # the CPU
        [_Dev([x, y, 0]) for y in range(3) for x in range(3)],   # odd
        [_Dev([x, 0, 0]) for x in range(4)],              # a line
        [_Dev([0, 0, 0]) for _ in range(4)]])             # one chip
    def test_rank_order_without_a_cycle(self, devs):
        assert ops.ring_order(devs) == list(range(len(devs)))

    def test_cpu_devices(self):
        assert ops.ring_order(jax.devices()[:4]) == [0, 1, 2, 3]
