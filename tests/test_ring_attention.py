"""Sequence-parallel attention (ring + Ulysses) — the long-context
first-class workload, validated exactly against unsharded attention."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ucc_tpu.examples.ring_attention import (  # noqa: E402
    make_ring_attention, make_ulysses_attention, reference_attention)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.make_mesh((8,), ("sp",))


def _inputs(heads, seq, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (heads, seq, d), jnp.float32)
    k = jax.random.normal(ks[1], (heads, seq, d), jnp.float32)
    v = jax.random.normal(ks[2], (heads, seq, d), jnp.float32)
    return q, k, v


class TestRingAttention:
    @pytest.mark.parametrize("seq", [64, 256])
    def test_exact_vs_reference(self, mesh, seq):
        heads, d = 4, 16
        q, k, v = _inputs(heads, seq, d)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "sp", None))
        qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))
        ring = make_ring_attention(mesh)
        out = np.asarray(jax.device_get(ring(qs, ks_, vs)))
        expect = np.asarray(reference_attention(q, k, v))
        np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)

    def test_memory_scaling_shape(self, mesh):
        # each shard sees only seq/8 of K/V at a time: the jitted program
        # must accept a sequence too large to attend monolithically if
        # materialized as (seq, seq) scores on one shard boundary check
        heads, seq, d = 2, 512, 8
        q, k, v = _inputs(heads, seq, d, seed=3)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "sp", None))
        ring = make_ring_attention(mesh)
        out = ring(*(jax.device_put(x, sh) for x in (q, k, v)))
        assert out.shape == (heads, seq, d)
        expect = np.asarray(reference_attention(q, k, v))
        np.testing.assert_allclose(np.asarray(jax.device_get(out)), expect,
                                   rtol=2e-4, atol=2e-5)


class TestUlyssesAttention:
    def test_exact_vs_reference(self, mesh):
        heads, seq, d = 8, 128, 16   # heads % 8 == 0
        q, k, v = _inputs(heads, seq, d, seed=1)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "sp", None))
        qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))
        uly = make_ulysses_attention(mesh)
        out = np.asarray(jax.device_get(uly(qs, ks_, vs)))
        expect = np.asarray(reference_attention(q, k, v))
        np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)


class TestFusedRingFlashAttention:
    """The Pallas-fused tier (ucc_tpu/fused_attention.py): K/V rotation
    as in-kernel remote DMAs overlapping the flash block update —
    validated exactly against full softmax(QK^T)V (interpret mode on the
    CPU mesh; tests/test_tpu_compile.py compiles the Mosaic path)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_exact_vs_reference(self, mesh, causal):
        from ucc_tpu.fused_attention import make_ring_flash_attention
        heads, seq, d = 2, 64, 8
        q, k, v = _inputs(heads, seq, d, seed=5)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "sp", None))
        fn = make_ring_flash_attention(mesh, causal=causal, axis="sp")
        out = np.asarray(jax.device_get(
            fn(*(jax.device_put(x, sh) for x in (q, k, v)))))
        s = np.einsum("hqd,hkd->hqk", np.asarray(q), np.asarray(k)) \
            / np.sqrt(d)
        if causal:
            mask = np.tril(np.ones((seq, seq), bool))
            s = np.where(mask[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        expect = np.einsum("hqk,hkd->hqd", p, np.asarray(v))
        np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)

    def test_matches_xla_tier(self, mesh):
        """Both context-parallel tiers must agree (same math, different
        schedules)."""
        from ucc_tpu.fused_attention import make_ring_flash_attention
        heads, seq, d = 4, 128, 16
        q, k, v = _inputs(heads, seq, d, seed=6)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "sp", None))
        args = tuple(jax.device_put(x, sh) for x in (q, k, v))
        fused = np.asarray(jax.device_get(
            make_ring_flash_attention(mesh, axis="sp")(*args)))
        xla = np.asarray(jax.device_get(make_ring_attention(mesh)(*args)))
        np.testing.assert_allclose(fused, xla, rtol=2e-4, atol=2e-5)

    def test_bf16_io_f32_accum(self, mesh):
        from ucc_tpu.fused_attention import make_ring_flash_attention
        heads, seq, d = 2, 64, 8
        q, k, v = _inputs(heads, seq, d, seed=7)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "sp", None))
        fn = make_ring_flash_attention(mesh, axis="sp")
        out = np.asarray(jax.device_get(
            fn(*(jax.device_put(x, sh) for x in (qb, kb, vb)))
            ).astype(np.float32))
        expect = np.asarray(reference_attention(q, k, v))
        # bf16 inputs, f32 accumulation: ~1e-2 tolerance
        np.testing.assert_allclose(out, expect, rtol=5e-2, atol=5e-2)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_vs_full_attention(self, mesh, causal):
        """custom_vjp: fused forward, lax ring-schedule backward — grads
        must match differentiating the full softmax(QK^T)V."""
        import contextlib
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ucc_tpu.fused_attention import ring_flash_attention
        heads, seq, d = 2, 24, 4
        q, k, v = _inputs(heads, seq, d, seed=9)
        sh = NamedSharding(mesh, P(None, "sp", None))
        qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))

        def body(a, b, c):
            return ring_flash_attention(a, b, c, axis_name="sp",
                                        causal=causal)
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, "sp", None),) * 3,
                          out_specs=P(None, "sp", None), check_vma=False)

        @jax.jit
        def loss(a, b, c):
            return jnp.sum(f(a, b, c) ** 2)

        def loss_ref(a, b, c):
            s = jnp.einsum("hqd,hkd->hqk", a, b) / jnp.sqrt(jnp.float32(d))
            if causal:
                m = jnp.tril(jnp.ones((seq, seq), bool))
                s = jnp.where(m[None], s, -jnp.inf)
            p = jax.nn.softmax(s, -1)
            return jnp.sum(jnp.einsum("hqk,hkd->hqd", p, c) ** 2)

        ctx = jax.set_mesh(mesh) if hasattr(jax, "set_mesh") \
            else contextlib.nullcontext()
        with ctx:
            g1 = jax.grad(loss, argnums=(0, 1, 2))(qs, ks_, vs)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestLongContextTraining:
    """End-to-end long-context training step (examples/long_context.py):
    fused/sp attention inside a dp×sp jitted train step, gradients
    through the custom_vjp, DP sync via ops.allreduce."""

    def test_loss_decreases(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from ucc_tpu.examples.long_context import (init_params,
                                                   make_train_step)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((2, 4), ("dp", "sp"))
        params = init_params(heads=2, d=4)
        kx, ky = jax.random.split(jax.random.PRNGKey(3))
        x = jax.random.normal(kx, (4, 2, 32, 4), jnp.float32)
        y = jax.random.normal(ky, (4, 2, 32, 4), jnp.float32) * 0.1
        xs = NamedSharding(mesh, P("dp", None, "sp", None))
        x, y = jax.device_put(x, xs), jax.device_put(y, xs)
        step = make_train_step(mesh, lr=0.05)
        w = [params["wq"], params["wk"], params["wv"], params["wo"]]
        losses = []
        for _ in range(6):
            out = step(*w, x, y)
            losses.append(float(jax.device_get(out[0])))
            w = list(out[1:])
        assert losses[-1] < losses[0], losses

    def test_grads_match_dense(self):
        """The applied update must equal -lr * (gradient of the GLOBAL
        mean loss), identically on every device — pins the sp-axis
        weight-gradient reduction (weight grads are per-rank partials;
        the ring backward only aggregates dK/dV, so without the sp
        allreduce the 'replicated' params silently diverge)."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from ucc_tpu.examples.long_context import (init_params,
                                                   make_train_step)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((2, 4), ("dp", "sp"))
        heads, d, batch, seq = 2, 4, 4, 32
        params = init_params(heads, d)
        kx, ky = jax.random.split(jax.random.PRNGKey(3))
        x = jax.random.normal(kx, (batch, heads, seq, d), jnp.float32)
        y = jax.random.normal(ky, (batch, heads, seq, d),
                              jnp.float32) * 0.1

        def dense_loss(wq, wk, wv, wo):
            q = jnp.einsum("bhsd,hde->bhse", x, wq)
            k = jnp.einsum("bhsd,hde->bhse", x, wk)
            v = jnp.einsum("bhsd,hde->bhse", x, wv)
            scores = jnp.einsum("bhse,bhte->bhst", q, k) / np.sqrt(d)
            mask = jnp.tril(jnp.ones((seq, seq), bool))
            p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            attn = jnp.einsum("bhst,bhte->bhse", p, v)
            out = jnp.einsum("bhse,hed->bhsd", attn, wo)
            return jnp.mean((out - y) ** 2)

        w = (params["wq"], params["wk"], params["wv"], params["wo"])
        ref = jax.grad(dense_loss, argnums=(0, 1, 2, 3))(*w)
        lr = 0.05
        xs = NamedSharding(mesh, P("dp", None, "sp", None))
        out = make_train_step(mesh, lr=lr)(
            *w, jax.device_put(x, xs), jax.device_put(y, xs))
        for name, new, old, g in zip(("wq", "wk", "wv", "wo"),
                                     out[1:], w, ref):
            shards = [np.asarray(s.data) for s in new.addressable_shards]
            for s in shards[1:]:       # truly replicated after update
                np.testing.assert_array_equal(s, shards[0], err_msg=name)
            np.testing.assert_allclose(
                shards[0], np.asarray(old - lr * g), rtol=1e-4,
                atol=1e-6, err_msg=name)

    def test_multi_axis_fallback_matches_fused(self, mesh):
        """ring_flash_attention under a multi-axis mesh silently takes
        the lax ring schedule; results must match the 1-axis fused path."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ucc_tpu.fused_attention import ring_flash_attention
        heads, seq, d = 2, 32, 8
        q, k, v = _inputs(heads, seq, d, seed=12)
        # 1-axis fused
        sh1 = NamedSharding(mesh, P(None, "sp", None))
        f1 = jax.shard_map(
            lambda a, b, c: ring_flash_attention(a, b, c, axis_name="sp"),
            mesh=mesh, in_specs=(P(None, "sp", None),) * 3,
            out_specs=P(None, "sp", None), check_vma=False)
        out1 = np.asarray(jax.device_get(jax.jit(f1)(
            *(jax.device_put(t, sh1) for t in (q, k, v)))))
        # 2-axis mesh (fallback path), sp size 4
        mesh2 = jax.make_mesh((2, 4), ("dp", "sp"))
        sh2 = NamedSharding(mesh2, P(None, "sp", None))
        f2 = jax.shard_map(
            lambda a, b, c: ring_flash_attention(a, b, c, axis_name="sp"),
            mesh=mesh2, in_specs=(P(None, "sp", None),) * 3,
            out_specs=P(None, "sp", None), check_vma=False)
        out2 = np.asarray(jax.device_get(jax.jit(f2)(
            *(jax.device_put(t, sh2) for t in (q, k, v)))))
        np.testing.assert_allclose(out1, out2, rtol=2e-5, atol=2e-6)


class TestGroupedQueryAttention:
    """GQA: q heads grouped over fewer K/V heads — the ring rotates only
    the kv_heads blocks (heads/kv_heads less ICI traffic). Validated
    against dense attention with K/V heads repeated per group."""

    @staticmethod
    def _gqa_inputs(h, h_kv, seq, d, seed=21):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (h, seq, d), jnp.float32)
        k = jax.random.normal(ks[1], (h_kv, seq, d), jnp.float32)
        v = jax.random.normal(ks[2], (h_kv, seq, d), jnp.float32)
        return q, k, v

    @staticmethod
    def _dense(q, k, v, causal):
        h, seq, d = q.shape
        g = h // k.shape[0]
        kr = np.repeat(np.asarray(k), g, axis=0)
        vr = np.repeat(np.asarray(v), g, axis=0)
        s = np.einsum("hqd,hkd->hqk", np.asarray(q), kr) / np.sqrt(d)
        if causal:
            mask = np.tril(np.ones((seq, seq), bool))
            s = np.where(mask[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("hqk,hkd->hqd", p, vr)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h,h_kv", [(4, 2), (8, 2), (6, 6)])
    def test_exact_vs_dense(self, mesh, causal, h, h_kv):
        from ucc_tpu.fused_attention import make_ring_flash_attention
        seq, d = 64, 8
        q, k, v = self._gqa_inputs(h, h_kv, seq, d)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "sp", None))
        fn = make_ring_flash_attention(mesh, causal=causal, axis="sp")
        out = np.asarray(jax.device_get(
            fn(*(jax.device_put(x, sh) for x in (q, k, v)))))
        np.testing.assert_allclose(out, self._dense(q, k, v, causal),
                                   rtol=2e-4, atol=2e-5)

    def test_mismatched_heads_rejected(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ucc_tpu.fused_attention import ring_flash_attention
        q, k, v = self._gqa_inputs(5, 2, 16, 4)   # 5 % 2 != 0
        sh = NamedSharding(mesh, P(None, "sp", None))

        def body(a, b, c):
            return ring_flash_attention(a, b, c, axis_name="sp")
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, "sp", None),) * 3,
                          out_specs=P(None, "sp", None), check_vma=False)
        with pytest.raises(ValueError, match="GQA"):
            f(*(jax.device_put(x, sh) for x in (q, k, v)))

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_vs_dense(self, mesh, causal):
        """Group-summed dK/dV: differentiating through jnp.repeat in the
        dense reference gives exactly the per-group gradient sums the
        ring backward must produce."""
        import contextlib
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ucc_tpu.fused_attention import ring_flash_attention
        h, h_kv, seq, d = 4, 2, 24, 4
        q, k, v = self._gqa_inputs(h, h_kv, seq, d, seed=23)
        sh = NamedSharding(mesh, P(None, "sp", None))
        qs, ks_, vs = (jax.device_put(x, sh) for x in (q, k, v))

        def body(a, b, c):
            return ring_flash_attention(a, b, c, axis_name="sp",
                                        causal=causal)
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P(None, "sp", None),) * 3,
                          out_specs=P(None, "sp", None), check_vma=False)

        @jax.jit
        def loss(a, b, c):
            return jnp.sum(f(a, b, c) ** 2)

        def loss_ref(a, b, c):
            g = h // h_kv
            kr = jnp.repeat(b, g, axis=0)
            vr = jnp.repeat(c, g, axis=0)
            s = jnp.einsum("hqd,hkd->hqk", a, kr) / jnp.sqrt(jnp.float32(d))
            if causal:
                m = jnp.tril(jnp.ones((seq, seq), bool))
                s = jnp.where(m[None], s, -jnp.inf)
            p = jax.nn.softmax(s, -1)
            return jnp.sum(jnp.einsum("hqk,hkd->hqd", p, vr) ** 2)

        ctx = jax.set_mesh(mesh) if hasattr(jax, "set_mesh") \
            else contextlib.nullcontext()
        with ctx:
            g1 = jax.grad(loss, argnums=(0, 1, 2))(qs, ks_, vs)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestGqaLongContextTraining:
    """GQA token-stream train step (examples/long_context.py round-5
    variant): 8 q heads over 2 kv heads on a dp x sp mesh — the ring
    rotates 4x less K/V; loss must decrease through the grouped
    custom_vjp backward + joint-axis weight sync."""

    def test_loss_decreases(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from ucc_tpu.examples.long_context import (init_gqa_params,
                                                   make_gqa_train_step)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((2, 4), ("dp", "sp"))
        heads, kv_heads, e, dm = 8, 2, 4, 16
        params = init_gqa_params(dm, heads, kv_heads, e)
        kx, ky = jax.random.split(jax.random.PRNGKey(5))
        x = jax.random.normal(kx, (4, 32, dm), jnp.float32)
        y = jax.random.normal(ky, (4, 32, dm), jnp.float32) * 0.1
        xs = NamedSharding(mesh, P("dp", "sp", None))
        x, y = jax.device_put(x, xs), jax.device_put(y, xs)
        step = make_gqa_train_step(mesh, heads, kv_heads, e, lr=0.05)
        w = [params["wq"], params["wk"], params["wv"], params["wo"]]
        losses = []
        for _ in range(6):
            out = step(*w, x, y)
            losses.append(float(jax.device_get(out[0])))
            w = list(out[1:])
        assert losses[-1] < losses[0], losses
