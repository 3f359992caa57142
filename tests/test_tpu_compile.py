"""The fleet's Pallas kernels compiled (not interpreted) for a described
TPU v5e chip, at the paper CNN's width.

Nothing runs: each test lowers a kernel with ``interpret=False`` against a
v5e topology that the installed TPU compiler can describe without a chip
attached, compiles it, and checks that the program holds a Mosaic
``tpu_custom_call`` named after its kernel (the `pallas_call`'s ``name``,
which a profile's op names carry).  This catches what interpret mode hides: block shapes
that do not tile, casts the chip has no instruction for, VMEM overruns.

The topology is described inside a module fixture, never at import, so
that every pytest-xdist worker collects the same tests and only the worker
given this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ldp_noise import ldp_perturb_fleet
from repro.kernels.sparsify import sparsify_fleet
from repro.kernels.upload_fused import upload_fused_fleet
from repro.kernels.window_fold import window_fold_fleet
from repro.kernels.wire_bytes import nnz_fleet
from repro.models.cnn import cnn_flat_layout

COHORT = 64
# the `name=` each kernel's pallas_call gives its custom call
KERNEL_NAMES = {"upload_fused": "upload_fused", "window_fold": "window_fold",
                "nnz": "wire_bytes", "sparsify": "sparsify",
                "ldp_perturb": "ldp_noise"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: a program
    compiled for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _calls(c, p, bounds):
    """name -> (kernel call with interpret=False, argument shapes/dtypes)."""
    f32, i32 = jnp.float32, jnp.int32
    return {
        "upload_fused": (
            lambda f, r, t, s, sc: upload_fused_fleet(
                f, r, t, s, sc, 0.05, 1.0, boundaries=bounds,
                need_nnz=True, interpret=False),
            [((c, p), f32), ((c, p), f32), ((c, len(bounds)), f32),
             ((c,), i32), ((c,), f32)]),
        "window_fold": (
            lambda pf, om, g, a, b: window_fold_fleet(pf, om, g, a, b,
                                                      interpret=False),
            [((p,), f32), ((c, p), f32), ((c,), i32), ((c,), f32),
             ((c,), f32)]),
        "nnz": (lambda f: nnz_fleet(f, interpret=False), [((c, p), f32)]),
        "sparsify": (
            lambda g, r, t: sparsify_fleet(g, r, t, interpret=False),
            [((c, p), f32), ((c, p), f32), ((c,), f32)]),
        "ldp_perturb": (
            lambda f, s, sc: ldp_perturb_fleet(f, s, sc, 0.05, 1.0,
                                               interpret=False),
            [((c, p), f32), ((c,), i32), ((c,), f32)]),
    }


@pytest.mark.parametrize("name", ["upload_fused", "window_fold", "nnz",
                                  "sparsify", "ldp_perturb"])
def test_fleet_kernel_compiles_for_v5e(one_chip, name):
    p, bounds = cnn_flat_layout((28, 28))
    assert len(bounds) == 6
    fn, arg_shapes = _calls(COHORT, p, bounds)[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in arg_shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert re.search(rf"%{KERNEL_NAMES[name]}(\.\d+)? = .* custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"',
                     compiled.as_text())
