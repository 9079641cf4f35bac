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
