"""The main path's Pallas kernels compile for a TPU v5e at real shapes.

Compiles for a described (not attached) v5e chip: what the TPU compiler
refuses — block shapes off the (8, 128) tiling, more SMEM or VMEM than a
kernel may use — fails here, where interpret mode accepts it.  Shapes are
SSB SF1's: the P* star's part/supplier/date partials (200000 / 2000 / 2555
rows), the top serving bucket (512 rows) and the 6M-row fact axis.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_star_gather, tree_predict

SF1_ARM_ROWS = (200_000, 2_000, 2_555)   # part, supplier, date
TOP_BUCKET = 512
SF1_FACT_ROWS = 6_000_000
# (k, depth) of the P3 and P4 tree heads (data/ssb_queries.py).
TREES = {"P3": (5, 3), "P4": (3, 2)}
# The paper's depth-7 trees over Table 4's sf-8 fact (Fig. 19's k = 16, and
# k = 256 projected to the features its 127 nodes read, about 101).
TABLE4_FACT_ROWS = 4_800_000
# A wide tree besides: setting 2's depth 9 over k = 512 (p = 511, l = 512,
# four leaf blocks), where the row block shrinks to fit VMEM.
TABLE4_TREES = {"k16": (16, 7), "k256-projected": (101, 7),
                "k512-depth9": (512, 9)}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent compile
    cache off (a described chip's executables cannot be read back)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _gather(ptrs, found, t0, t1, t2):
    return fused_star_gather(ptrs, found, [t0, t1, t2])


@pytest.mark.parametrize("n", [TOP_BUCKET, SF1_FACT_ROWS],
                         ids=["top-bucket", "sf1-fact"])
def test_fused_star_gather_compiles_for_v5e(one_chip, n):
    """J=3 P1 partials (l=4, padded to 128 lanes); the fact-sized batch
    maps over SMEM-sized chunks."""
    args = ([_spec(one_chip, (3, n), jnp.int32)] * 2
            + [_spec(one_chip, (r, 4)) for r in SF1_ARM_ROWS])
    compiled = jax.jit(_gather).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [TOP_BUCKET, SF1_FACT_ROWS],
                         ids=["top-bucket", "sf1-fact"])
@pytest.mark.parametrize("head", sorted(TREES))
def test_tree_predict_compiles_for_v5e(one_chip, head, n):
    k, depth = TREES[head]
    p, l = 2 ** depth - 1, 2 ** depth
    args = [_spec(one_chip, s) for s in
            ((n, k), (k, p), (p,), (p, l), (l,))]
    compiled = jax.jit(tree_predict).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("head", sorted(TABLE4_TREES))
def test_tree_predict_compiles_for_v5e_at_table4_fact(one_chip, head):
    """One call over the whole 4.8M-row fact, in the row blocks
    ``row_block`` chooses from the shapes (the last one ragged): p = 127
    nodes and l = 128 leaves (one leaf block), or the wide tree's 511 and
    512."""
    k, depth = TABLE4_TREES[head]
    n, p, l = TABLE4_FACT_ROWS, 2 ** depth - 1, 2 ** depth
    args = [_spec(one_chip, s) for s in
            ((n, k), (k, p), (p,), (p, l), (l,))]
    compiled = jax.jit(tree_predict).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
