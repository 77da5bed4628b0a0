"""Compile the main-path kernels and the four-chip mesh encode for a
described TPU v5e, at real widths, without a chip.

The TPU compiler refuses here what it would refuse on the chip (layouts,
unsupported ops, VMEM), so these tests guard every change to the kernels
at no chip time.  The programs are compiled for the described devices, so
`kernels.ops` lowers its platform switch to the compiled Pallas kernels,
and each kernel test asserts that the program holds a `tpu_custom_call`.
The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of one while these tests run
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_gf_matmul_compiles(one_chip):
    from repro.kernels.ops import gf_matmul

    text = _compiled_text(gf_matmul, _u32((128, 256), one_chip),
                          _u32((256, 65536), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("K,W", [(16, 1 << 19), (256, 1 << 16)])
def test_ntt_compiles(one_chip, K, W, inverse):
    from repro.kernels.ops import ntt

    text = _compiled_text(lambda x: ntt(x, inverse=inverse),
                          _u32((K, W), one_chip))
    assert "tpu_custom_call" in text


def test_ntt_encode_rs_16_4_compiles(one_chip):
    """The local encode executable of an rs 16+4 plan: the NTT fast path,
    whose transforms lower to the Pallas kernel for the chip."""
    from repro.api import CodeSpec, Encoder
    from repro.api.backends import local_encode_callable

    plan = Encoder.plan(CodeSpec(kind="rs", K=16, R=4), backend="local")
    assert plan.local_impl == "ntt"
    fn = local_encode_callable(plan)
    text = fn.lower(_u32((16, 1 << 19), one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_ntt_encode_rs_10_4_compiles(one_chip):
    """HDFS RS-10-4 at its 1 MiB cells: the shortened code's NTT encode
    (3 blocks of 4, two zero rows appended in the program) compiles to the
    Pallas kernel and takes only the 10 uploaded rows."""
    from repro.api import CodeSpec, Encoder
    from repro.api.backends import local_encode_callable

    plan = Encoder.plan(CodeSpec(kind="rs", K=10, R=4), backend="local")
    assert plan.local_impl == "ntt"
    fn = local_encode_callable(plan)
    text = fn.lower(_u32((10, 1 << 19), one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_mesh_encode_rs_4_4_compiles(topo, monkeypatch):
    """The rs 4+4 mesh encode on four described chips, one source per
    chip: the schedule's rounds lower to collective-permutes."""
    import repro.api.backends as backends
    from repro.api import CodeSpec, Encoder, cache_clear

    devs = list(topo.devices[:4])
    monkeypatch.setattr(backends, "_require_devices", lambda n: devs[:n])
    monkeypatch.setattr(backends.MeshBackend, "device_requirement",
                        lambda self, spec: 0)
    try:
        plan = Encoder.plan(CodeSpec(kind="rs", K=4, R=4), backend="mesh")
        x = _u32((4, 1 << 22), backends.mesh_sharding(plan))
        compiled = plan.mesh_callable().lower(x).compile()
    finally:
        cache_clear()  # the plan holds described devices: keep it private
    assert "collective-permute" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device == (1 << 22) * 4
