"""Compile the Pallas kernels of the main path for a described TPU v5e,
at the shapes the schedules feed them, without a chip.

Interpret mode on the CPU cannot show what Mosaic refuses (a value
``dynamic_slice``, more scoped VMEM than a kernel may use, a 64-bit
index under x64); the TPU compiler installed here can, for a chip that
is described and not attached.  Each case compiles one kernel in f32
and asserts the program holds it as a ``tpu_custom_call``.

The topology is described only inside the module fixture: only one
process at a time may load the TPU library, so describing it while a
module is imported would break the multi-worker test run.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from slate_tpu.ops.pallas import kernels as tk
from slate_tpu.ops.pallas import panel_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no chip compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# (kernel call, operand shapes): the nb_switch=256 bases and the panel /
# solve heights the n=4096-8192 schedules reach
CASES = {
    "chol_base": (pk.chol_base_pallas, [(256, 256)]),
    "panel_lu": (pk.panel_lu_pallas, [(4096, 256)]),
    "trsm_lower": (pk.trsm_lower_pallas, [(2048, 2048), (2048, 512)]),
    "trsm_upper": (pk.trsm_upper_pallas, [(2048, 2048), (2048, 512)]),
    "larft": (pk.larft_pallas, [(4096, 256), (256,)]),
    "syrk_diag": (pk.syrk_diag_pallas, [(256, 256), (256, 256)]),
    "gemm_sub": (
        pk.gemm_sub_pallas, [(1024, 1024), (1024, 2048), (1024, 2048)]
    ),
    "tile_norms": (
        lambda T: tk.tile_norms_pallas(T, "fro_sumsq"), [(64, 512, 512)]
    ),
    "butterfly_level": (
        lambda X, d1, d2: tk.butterfly_level_pallas(X, d1, d2, True),
        [(8192, 512), (4096,), (4096,)],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [
        jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
        for s in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
