"""Distributed PFFT correctness on fake multi-device meshes.

Device count is locked at first jax init, so the multi-device cases run in
a subprocess with XLA_FLAGS set; the in-process tests cover the 1-device
degenerate mesh.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, {src!r})
import numpy as np, jax, jax.numpy as jnp
from repro.core.pfft_dist import pfft2_distributed, make_pfft2_fn, ragged_row_layout
from repro.plan import PlanConfig

mesh = jax.make_mesh((8,), ("fft",))
rng = np.random.default_rng(3)
m = (rng.standard_normal((64, 64)) + 1j*rng.standard_normal((64, 64))).astype(np.complex64)
m = jnp.asarray(m)
ref = jnp.fft.fft2(m)

out = pfft2_distributed(m, mesh, "fft")
assert float(jnp.max(jnp.abs(out - ref))) < 1e-2, "plain"

out = pfft2_distributed(m, mesh, "fft", padded="czt")
assert float(jnp.max(jnp.abs(out - ref))) < 1e-2, "czt"

out = pfft2_distributed(m, mesh, "fft", config=PlanConfig(radix=2))
assert float(jnp.max(jnp.abs(out - ref))) < 1e-2, "stockham"

out = make_pfft2_fn(mesh, 64)(m)
assert float(jnp.max(jnp.abs(out - ref))) < 1e-2, "jit"

# padded='crop' = padded-signal DFT semantics; compare vs that oracle
pad = 80
out = pfft2_distributed(m, mesh, "fft", padded="crop", pad_len=pad)
def crop_phase(mat):
    t = jnp.fft.fft(jnp.pad(mat, ((0,0),(0,pad-64))), axis=-1)[:, :64]
    return t
ref2 = crop_phase(crop_phase(m).T).T
assert float(jnp.max(jnp.abs(out - ref2))) < 1e-2, "crop semantics"

rows, counts = ragged_row_layout(np.array([10, 6, 8, 8, 8, 8, 8, 8]), 8)
assert rows == 10 and counts.sum() == 64

# software-pipelined panels: identical result to the monolithic phase
for k in (2, 4, 8):
    out = pfft2_distributed(m, mesh, "fft", config=PlanConfig(pipeline_panels=k))
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-2, "panels %d" % k
out = pfft2_distributed(m, mesh, "fft", config=PlanConfig(pad="czt", pipeline_panels=4))
assert float(jnp.max(jnp.abs(out - ref))) < 1e-2, "czt panels"
out = pfft2_distributed(m, mesh, "fft", pad_len=pad,
                        config=PlanConfig(pad="fpm", pipeline_panels=2))
assert float(jnp.max(jnp.abs(out - ref2))) < 1e-2, "crop panels"
try:
    pfft2_distributed(m, mesh, "fft", config=PlanConfig(pipeline_panels=3))
    raise SystemExit("expected ValueError for non-dividing panel count")
except ValueError:
    pass
print("DIST_OK")
"""


def test_distributed_pfft_8_devices():
    code = SCRIPT.format(src=os.path.abspath(SRC))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    assert "DIST_OK" in proc.stdout, proc.stderr[-2000:]


def _interleave_reference(gathered, *, c, fused):
    """The per-panel interleave the pipelined phase used before: each
    panel transposed (unfused), cut into peers, stacked on axis 2."""
    tiles = [g if fused else g.T for g in gathered]
    rows_out, k = tiles[0].shape[0], len(tiles)
    p = tiles[0].shape[1] // c
    out = jnp.stack([t.reshape(rows_out, p, c) for t in tiles], axis=2)
    return out.reshape(rows_out, p * k * c)


@pytest.mark.parametrize("dtype", [np.float32, np.complex64],
                         ids=["f32", "c64"])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
@pytest.mark.parametrize("p", [1, 2, 4])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_interleave_panels_matches_per_panel_stack(k, p, fused, dtype):
    """The pipelined phase's re-interleave puts every exchanged element
    where the per-panel formula did, bit for bit."""
    from repro.core.pfft_dist import _interleave_panels
    c, rows_out = 3, 16
    shape = (rows_out, p * c) if fused else (p * c, rows_out)
    size = int(np.prod(shape))
    gathered = []
    for i in range(k):
        v = np.arange(i * size, (i + 1) * size, dtype=np.float32)
        if dtype == np.complex64:
            v = v + 1j * (v + 0.5)
        gathered.append(jnp.asarray(v.astype(dtype).reshape(shape)))
    got = _interleave_panels(gathered, c=c, fused=fused)
    want = _interleave_reference(gathered, c=c, fused=fused)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pipelined_equals_monolithic_4_devices(dist_subprocess):
    """On 4 devices, 8 panels give the monolithic phase's transform,
    over the flat and the hierarchical (2 hosts x 2) exchange."""
    dist_subprocess("""
import numpy as np, jax.numpy as jnp
from repro.core.pfft_dist import pfft2_distributed
from repro.launch.mesh import make_fft_mesh
from repro.plan import PlanConfig
rng = np.random.default_rng(5)
m = jnp.asarray((rng.standard_normal((64, 64))
                 + 1j * rng.standard_normal((64, 64))).astype(np.complex64))
ref = np.fft.fft2(np.asarray(m))
for exchange, mesh in (("flat", make_fft_mesh(4)),
                       ("hier", make_fft_mesh(hosts=2, local=2))):
    one, eight = (np.asarray(pfft2_distributed(
        m, mesh, "fft", config=PlanConfig(pipeline_panels=k,
                                          exchange=exchange)))
        for k in (1, 8))
    assert np.abs(eight - one).max() < 1e-2, exchange
    assert np.abs(eight - ref).max() < 1e-2, exchange
print("OK")
""")


def test_distributed_pfft_single_device_mesh():
    mesh = jax.make_mesh((1,), ("fft",))
    from repro.core.pfft_dist import pfft2_distributed
    rng = np.random.default_rng(0)
    m = jnp.asarray((rng.standard_normal((32, 32))
                     + 1j * rng.standard_normal((32, 32))).astype(np.complex64))
    out = pfft2_distributed(m, mesh, "fft")
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.fft.fft2(m)),
                               atol=1e-2)


def test_pipelined_single_device_mesh():
    """pipeline_panels on the degenerate 1-device mesh (pure reshuffle)."""
    mesh = jax.make_mesh((1,), ("fft",))
    from repro.core.pfft_dist import pfft2_distributed
    rng = np.random.default_rng(1)
    m = jnp.asarray((rng.standard_normal((32, 32))
                     + 1j * rng.standard_normal((32, 32))).astype(np.complex64))
    from repro.plan import PlanConfig
    out = pfft2_distributed(m, mesh, "fft", config=PlanConfig(pipeline_panels=4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.fft.fft2(m)),
                               atol=1e-2)


def test_unknown_axis_raises():
    from repro.core.pfft_dist import pfft2_distributed
    with pytest.raises(KeyError):
        pfft2_distributed(jnp.ones((32, 32), jnp.complex64),
                          jax.make_mesh((1,), ("fft",)), "nope")


def test_local_phase_refuses_silent_monolithic_fallback():
    """Satellite regression: a panel count that doesn't divide the local
    rows used to fall back to the monolithic phase silently — a direct
    caller (or tuner drift) would time/run a different program than
    requested.  Now it's a named error, raised before any lax op."""
    from repro.core.pfft_dist import _local_phase
    from repro.plan import PlanConfig
    block = jnp.ones((16, 16), jnp.complex64)
    with pytest.raises(ValueError, match="divide local rows"):
        _local_phase(block, "fft", 16, padded=None, pad_len=16,
                     config=PlanConfig(), pipeline_panels=3)
    # pfft2_distributed still validates up front with its own message
    from repro.core.pfft_dist import pfft2_distributed
    with pytest.raises(ValueError, match="divide local rows"):
        pfft2_distributed(jnp.ones((32, 32), jnp.complex64),
                          jax.make_mesh((1,), ("fft",)), "fft",
                          config=PlanConfig(pipeline_panels=3))
