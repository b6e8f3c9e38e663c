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
import re

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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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


@pytest.fixture(scope="module")
def slab_program(topo, no_persistent_cache):
    """The four-chip cell's program at N = 32768, as the chip plans it
    (8 panels), compiled for a described 2x2: ``(plan.counters(), the
    executable's HLO text)``."""
    import numpy as np
    from jax.sharding import Mesh
    import repro.kernels.fft.ops as fft_ops
    from repro import obs
    from repro.core import plan_pfft
    from repro.plan.config import PlanConfig
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fft_ops, "_on_cpu", lambda: False)
        plan = plan_pfft(32768, method="lb",
                         mesh=Mesh(np.array(topo.devices), ("fft",)),
                         config=PlanConfig(radix=4, batched=True,
                                           pipeline_panels=8))
        return plan.counters(), obs.compiled_text(plan._fn,
                                                  plan.input_spec())


def test_slab_exchange_counts_for_v5e_2x2(slab_program):
    """The TPU compiler carries each complex64 exchange as two f32
    all-to-alls (re and im), so the program's 2 phases x 8 panels count
    32 collectives; each device sends 2 x 3/4 of its 8 N^2/4 bytes off
    the device all the same."""
    from repro import obs
    n = 32768
    counts, _ = slab_program
    assert {k: counts[k] for k in (obs.COLLECTIVES, obs.EXCHANGE_BYTES)} \
        == {obs.COLLECTIVES: 32, obs.EXCHANGE_BYTES: 2 * 3 * 8 * n * n // 16}


_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (\S+) ([\w-]+)\(")


def test_slab_interleave_stays_dense_in_f32_planes(slab_program):
    """The pipelined phases re-interleave their exchanged panels without
    one-row tiles, and the program carries f32 planes from one split at
    the input to one ``X64Combine`` that writes the row-major complex64
    result: no complex copy moves a transpose past the join."""
    from repro import obs
    counts, text = slab_program
    assert counts.get(obs.SPARSE_TILES, 0) == 0
    for target, calls in (("X64SplitLow", 1), ("X64SplitHigh", 1),
                          ("X64Combine", 1)):
        assert text.count(f'custom_call_target="{target}"') == calls, target
    body = text[text.index("ENTRY"):].split("\n}")[0]
    found = [m.groups() for m in map(_INSTR.match, body.splitlines()) if m]
    c64 = [(name, op) for _, name, shape, op in found
           if shape.startswith("c64")]
    assert [op for _, op in c64] == ["parameter", "custom-call"], c64
    root = next((shape, op) for is_root, _, shape, op in found if is_root)
    assert root[1] == "custom-call" and root[0].endswith("{1,0:T(8,128)}")
