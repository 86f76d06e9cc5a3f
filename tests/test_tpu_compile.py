"""The main path's Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described by its topology and not attached. Each test
compiles one kernel at a real bucket size with ``interpret=False`` and
checks that the program holds the kernel as a ``tpu_custom_call`` — what
interpret-mode parity tests cannot show (tile alignment, lowerable
vector shapes, scalar-memory capacity).

The topology is described only inside the module fixture below, so
nothing loads the TPU library while tests are collected; the fixture
skips where no topology can be described.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.base_kernels import SquareExponential
from repro.kernels.xmv_block_sparse import RowPanelPack, xmv_gram_tile, \
    xmv_row_panel_batched
from repro.kernels.xmv_dense import DENSE_TILE, xmv_dense_batched

EK = SquareExponential(1.0, rank=12)
T = 8              # octile edge
N = 96             # the paper's synthetic graphs: 96 nodes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packs(sharding, B, n, dtype, t=T):
    nt = n // t
    k_max = nt        # every tile of a row occupied: the worst case
    return RowPanelPack(
        values_adj=_spec(sharding, (B, nt, k_max, t, t), dtype),
        values_lab=_spec(sharding, (B, nt, k_max, t, t), dtype),
        col=_spec(sharding, (B, nt, k_max), jnp.int32),
        count=_spec(sharding, (B, nt), jnp.int32))


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# bf16 packs: the benchmark's ``bf16-packs`` control (``pack_dtype``)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_row_panel_compiles(one_chip, dtype):
    """Whole-bucket row-panel matvec, B = 64 pairs of 96-node graphs,
    fused CG epilogue."""
    B, nt = 64, N // T
    packs = _packs(one_chip, B, N, dtype)
    P = _spec(one_chip, (B, nt, nt, T, T))
    _assert_kernel_compiles(
        lambda p1, p2, P, d: xmv_row_panel_batched(
            p1, p2, P, EK, diag=d, interpret=False),
        packs, packs, P, P)


# bf16 packs: the benchmark's ``bf16-packs`` control (``pack_dtype``)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gram_tile_compiles(one_chip, dtype):
    """8 x 8 Gram tile of 96-node graphs, fused CG epilogue."""
    nt = N // T
    packs = _packs(one_chip, 8, N, dtype)
    P = _spec(one_chip, (8, 8, nt, nt, T, T))
    _assert_kernel_compiles(
        lambda p1, p2, P, d: xmv_gram_tile(
            p1, p2, P, EK, diag=d, interpret=False),
        packs, packs, P, P)


# bf16 packs: the benchmark's ``bf16-packs`` control (``pack_dtype``)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gram_tile_compiles_at_the_picked_edge(one_chip, dtype):
    """The same 8 x 8 Gram tile at t = 32, the octile edge the Gram
    driver picks for 96-node buckets (``[32, 1024]`` accumulators)."""
    t = 32
    nt = N // t
    packs = _packs(one_chip, 8, N, dtype, t)
    P = _spec(one_chip, (8, 8, nt, nt, t, t))
    _assert_kernel_compiles(
        lambda p1, p2, P, d: xmv_gram_tile(
            p1, p2, P, EK, diag=d, interpret=False),
        packs, packs, P, P)


def test_dense_compiles(one_chip):
    """Dense kernel on a DrugBank-shaped bucket (64 pairs padded to 48
    nodes) with the traced edge-hyperparameter vector."""
    B, n = 64, 48
    nt = n // DENSE_TILE
    A = _spec(one_chip, (B, n, n))
    P = _spec(one_chip, (B, nt, nt, DENSE_TILE, DENSE_TILE))
    theta = _spec(one_chip, (1,))
    _assert_kernel_compiles(
        lambda a, e, P, d, th: xmv_dense_batched(
            a, e, a, e, P, EK, diag=d, theta=th, interpret=False),
        A, A, P, P, theta)
