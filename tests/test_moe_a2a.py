"""shard_map all-to-all MoE == einsum MoE (uses 1 host device mesh;
the 4-shard variant is covered in the dry-run at 256 devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.models.moe import moe_block, moe_block_gather
from repro.models.moe_shard_map import moe_block_a2a


@pytest.fixture(scope="module")
def operands():
    key = jax.random.PRNGKey(0)
    B, S, D, E, F = 4, 64, 32, 8, 16
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (B, S, D), jnp.float32),
            jax.random.normal(ks[1], (D, E)) * 0.1,
            jax.random.normal(ks[2], (E, D, F)) * 0.1,
            jax.random.normal(ks[3], (E, D, F)) * 0.1,
            jax.random.normal(ks[4], (E, F, D)) * 0.1)


def test_a2a_matches_einsum_dispatch(operands):
    x, rw, wg, wu, wd = operands
    mesh = jax.make_mesh((len(jax.devices()), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with mesh:
        y_ref, _ = moe_block(x, rw, wg, wu, wd, top_k=2,
                             capacity_factor=8.0, group_size=64)
        y, _ = jax.jit(lambda *a: moe_block_a2a(
            *a, top_k=2, capacity_factor=8.0, mesh=mesh,
            group_size=64))(x, rw, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_a2a_dropless_decode_matches_gather(operands):
    x, rw, wg, wu, wd = operands
    x1 = x[:, :1]                      # decode: S == 1
    mesh = jax.make_mesh((len(jax.devices()), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    E, K = 8, 2
    with mesh:
        y_ref, _ = moe_block_gather(x1, rw, wg, wu, wd, top_k=K)
        y, _ = jax.jit(lambda *a: moe_block_a2a(
            *a, top_k=K, capacity_factor=E / K, mesh=mesh,
            group_size=64))(x1, rw, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
