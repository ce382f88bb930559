"""The tiled warp of ``ops/augment.py`` compiled at its real sizes for
the chip it runs on, with no chip attached: what the TPU's compiler
would refuse (a shape it cannot lay out, more memory than the budget
meant) fails here and costs no chip time.  Nothing runs, so nothing
here is a time or a pixel: ``tests/test_augment_warp_dense.py`` has the
pixels, PERF.md the times.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fast_autoaugment_tpu.ops import augment as A


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e; the library is loaded here, by the one worker
    that runs this file, and never while a module is imported."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no compiler for the chip in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache and cannot be read back without the chip."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


# a step's batch at 224 px (`resnet50_imagenet_train`) and EfficientNet's largest conf
@pytest.mark.parametrize("n,size", [(128, 224), (64, 380)])
def test_tiled_warp_compiles_for_a_v5e_inside_its_budget(one_chip, no_compile_cache, n, size):
    assert A._warp_tiling(size, size) is not None
    imgs = jax.ShapeDtypeStruct((n, size, size, 3), jnp.float32, sharding=one_chip)
    mats = jax.ShapeDtypeStruct((n, 2, 3), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.vmap(A._warp_affine_nearest)).lower(imgs, mats).compile()
    # the chunks' intermediates, the output and the tiles' indices beside them
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < A._DENSE_WARP_BUDGET_BYTES + 3 * n * size * size * 3 * 4
    text = compiled.as_text()
    assert "conditional" in text  # the run-time choice is a branch, not a select
    assert A._warp_bytes_an_image(size, size, 3) * n > A._DENSE_WARP_BUDGET_BYTES
    assert "while" in text  # so the batch runs in chunks
