"""The row-FFT kernels compile for a TPU v5e that is described, not attached.

Each test lowers one public op at a real row length with the blocks
``pick_block_rows`` chooses and compiles it with the TPU compiler that
ships with jax; the program must hold the Pallas kernel
(``tpu_custom_call``).  Lengths whose smallest legal block outgrows VMEM
must refuse with ``KernelUnsupported`` instead.  Nothing runs: these are
compile checks, not chip runs.
"""

import functools
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.fft.ops import KernelUnsupported, fft_rows_op, tpu_unsupported
from repro.kernels.fft.real import rfft_rows_op
from repro.kernels.fused.ops import fft_rows_transpose_op
from repro.kernels.fused.real import rfft_rows_transpose_op

OPS = {
    "fft": (fft_rows_op, jnp.complex64),
    "fused": (fft_rows_transpose_op, jnp.complex64),
    "rfft": (rfft_rows_op, jnp.float32),
    "rfused": (rfft_rows_transpose_op, jnp.float32),
}
ROWS = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n", [256, 4096, 16384])
@pytest.mark.parametrize("kind", sorted(OPS))
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kind, n):
    op, dtype = OPS[kind]
    x = jax.ShapeDtypeStruct((ROWS, n), dtype, sharding=one_chip)
    lowered = jax.jit(functools.partial(op, interpret=False))
    if tpu_unsupported(kind, n) is not None:
        with pytest.raises(KernelUnsupported, match="VMEM"):
            lowered.lower(x)
        return
    compiled = lowered.lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_2d_program_for_v5e(one_chip, no_persistent_cache, monkeypatch):
    """The fused 2-D transform carries f32 planes between its two kernels:
    one split at the input, one ``X64Combine`` at the output, no join
    between the phases, and the kernels keep the op wrapper's name (the
    benchmark's row-FFT reader finds them by it)."""
    import repro.kernels.fft.ops as fft_ops
    from repro.fft.fft2d import fft2d_fused
    monkeypatch.setattr(fft_ops, "_on_cpu", lambda: False)
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.complex64, sharding=one_chip)
    text = jax.jit(fft2d_fused).lower(x).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels) == 2
    assert all(ln.split("=")[0].strip().lstrip("%").startswith(
        "fft_rows_transpose_op") for ln in kernels)
    assert text.count('custom_call_target="X64Combine"') == 1
    assert "multiply_add" not in text
