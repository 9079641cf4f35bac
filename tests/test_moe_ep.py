"""Expert-parallel MoE routing example: dispatch/combine alltoalls through
ucc_tpu.ops inside one jitted shard_map program (the EP strategy the
reference's MoE traffic-matrix generator models, ucc_pt_config.h:98-108)."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ucc_tpu.examples.moe_ep import make_moe_layer, reference_moe


def test_moe_ep_matches_reference():
    n = 4
    if len(jax.devices()) < n:
        pytest.skip("needs >= 4 devices")
    mesh = jax.make_mesh((n,), ("ep",))
    d, cap, tokens_per_dev = 8, 3, 6
    total = n * tokens_per_dev
    k = jax.random.PRNGKey(1)
    k1, k2, k3, k4 = jax.random.split(k, 4)
    x = jax.random.normal(k1, (total, d), jnp.float32)
    w_up = jax.random.normal(k2, (n, d, 16), jnp.float32) * 0.3
    w_dn = jax.random.normal(k3, (n, 16, d), jnp.float32) * 0.3
    assign = jax.random.randint(k4, (total,), 0, n, jnp.int32)

    layer = make_moe_layer(mesh, d, cap)
    sh = NamedSharding(mesh, P("ep"))
    y = layer(jax.device_put(x, sh), jax.device_put(w_up, sh),
              jax.device_put(w_dn, sh), jax.device_put(assign, sh))
    expect = reference_moe(x, w_up, w_dn, np.asarray(assign), cap)
    np.testing.assert_allclose(np.asarray(y), expect, rtol=2e-4, atol=2e-5)


def test_moe_ep_capacity_drop():
    """Tokens beyond a (source, expert) capacity produce zero outputs —
    the static-shape contract."""
    n = 4
    if len(jax.devices()) < n:
        pytest.skip("needs >= 4 devices")
    mesh = jax.make_mesh((n,), ("ep",))
    d, cap, tokens_per_dev = 4, 1, 4
    total = n * tokens_per_dev
    x = jnp.ones((total, d), jnp.float32)
    w_up = jnp.ones((n, d, 8), jnp.float32) * 0.1
    w_dn = jnp.ones((n, 8, d), jnp.float32) * 0.1
    assign = jnp.zeros((total,), jnp.int32)   # everyone -> expert 0
    layer = make_moe_layer(mesh, d, cap)
    sh = NamedSharding(mesh, P("ep"))
    y = np.asarray(layer(jax.device_put(x, sh), jax.device_put(w_up, sh),
                         jax.device_put(w_dn, sh),
                         jax.device_put(assign, sh)))
    # first token per device fits (capacity 1 per source), rest dropped
    for dev in range(n):
        blk = y[dev * tokens_per_dev:(dev + 1) * tokens_per_dev]
        assert np.abs(blk[0]).sum() > 0
        np.testing.assert_allclose(blk[1:], 0)


# ---------------------------------------------------------------------------
# dropless DeepSeek-V2 MoE layer over the eager API
# ---------------------------------------------------------------------------

#: DeepSeek-V2-Lite's routing (64 experts, top-6, 2 shared) at small widths
D, EXPERTS, TOPK, INTER, SHARED, TOKENS = 32, 16, 6, 24, 2, 20


@pytest.fixture(scope="module")
def ep_job():
    import sys
    import os
    sys.path.insert(0, os.path.dirname(__file__))
    from harness import UccJob
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    j = UccJob(4)
    yield j, j.create_team()
    j.cleanup()


def _params_and_tokens(seed):
    from ucc_tpu.examples.moe_ep import init_moe_params
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(k1, D, EXPERTS, INTER, SHARED)
    x = jax.random.normal(k2, (4 * TOKENS, D), jnp.float32)
    return params, x


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dropless_layer_matches_reference(ep_job, seed):
    """The layer over ucc_tpu (route, counts ALLTOALL, dispatch and
    combine ALLTOALLV, held experts, shared experts) against the plain
    float32 reference of the whole layer on all tokens.

    Tolerance: both compute in float32 at the highest matmul precision;
    they differ only in grouping (the layer runs each expert on the rows it
    received and sums the top-k after the exchange, the reference sums
    masked experts over every token), so they agree to float32 rounding of
    sums of ~10 terms: rtol 1e-5, atol 1e-6 (outputs are O(1)). An
    exchange that drops or misplaces a row is off by O(0.1)."""
    from ucc_tpu.examples.moe_ep import DroplessMoE, moe_reference
    job, teams = ep_job
    params, x = _params_and_tokens(seed)
    devs = [job.contexts[r].tl_contexts["xla"].obj.device for r in range(4)]
    with jax.default_matmul_precision("highest"):
        layer = DroplessMoE(teams, job.contexts, devs, params, TOPK)
        xs = [jax.device_put(x[r * TOKENS:(r + 1) * TOKENS], devs[r])
              for r in range(4)]
        got = np.concatenate([np.asarray(y) for y in layer(xs)])
        want = np.asarray(moe_reference(x, params, TOPK))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_dropless_layer_is_dropless_under_skew(ep_job):
    """A router that sends four of every token's six rows to the experts
    of rank 0: that rank receives two thirds of all rows, none dropped,
    where a capacity-padded layer at the even share would drop half of
    them."""
    from ucc_tpu.examples.moe_ep import DroplessMoE, moe_reference
    job, teams = ep_job
    params, x = _params_and_tokens(7)
    hot = jnp.full((EXPERTS,), -30.0).at[:EXPERTS // 4].set(0.0)
    params = dict(params, router=params["router"] * 0 + hot[None, :]
                  + 1e-3 * params["router"])
    devs = [job.contexts[r].tl_contexts["xla"].obj.device for r in range(4)]
    with jax.default_matmul_precision("highest"):
        layer = DroplessMoE(teams, job.contexts, devs, params, TOPK)
        xs = [jax.device_put(x[r * TOKENS:(r + 1) * TOKENS], devs[r])
              for r in range(4)]
        got = np.concatenate([np.asarray(y) for y in layer(xs)])
        want = np.asarray(moe_reference(x, params, TOPK))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_shares_add_up_to_the_uncut_layer():
    """One chip's share (its 4 of 16 routed experts, the router over all
    16) summed over the four chips, plus the shared experts counted once,
    is the whole layer: the cut of the benchmark's one-chip cell.

    Tolerance: the same float32 terms summed in another order, rtol 1e-5,
    atol 1e-6."""
    from ucc_tpu.examples.moe_ep import (moe_reference, shared_experts)
    params, x = _params_and_tokens(3)
    with jax.default_matmul_precision("highest"):
        shares = sum(moe_reference(x, params, TOPK,
                                   held=range(4 * r, 4 * r + 4),
                                   shared=False) for r in range(4))
        whole = moe_reference(x, params, TOPK)
        np.testing.assert_allclose(
            np.asarray(shares + shared_experts(x, params)),
            np.asarray(whole), rtol=1e-5, atol=1e-6)
        one = moe_reference(x, params, TOPK, held=range(4), shared=False)
        assert float(jnp.max(jnp.abs(one - (whole - shared_experts(
            x, params))))) > 1e-2        # a share alone is not the layer
