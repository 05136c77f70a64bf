"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with jax, compiles
each kernel at the shape its service uses for a chip that is described
and not attached. That catches what interpret mode cannot — blocks that
break the (8, 128) tiling, or more VMEM than a kernel may use — without
a chip. Each compiled program must hold the Mosaic kernel
(``tpu_custom_call``), not an XLA fallback.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.window_agg import window_aggregate
from repro.pipeline.queries import offload_aggregate

Q2_RECORDS = 120 * 86400          # one thing's 120-day history at 1 Hz


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("agg", ["mean", "max"])
def test_q2_offload_compiles(one_chip, agg):
    """The paper's 120-day Q2 window (81,000 × 128 folded) streams
    through VMEM in row segments instead of one window-sized block."""
    _compile(lambda x: offload_aggregate(x, agg=agg), one_chip,
             ((Q2_RECORDS,), F32))


@pytest.mark.parametrize("agg", ["mean", "max"])
def test_q2_offload_result_in_host_memory(one_chip, agg):
    """With ``result_on_host`` the program's own last step copies the
    scalar to host memory (memory space 5 in the entry's result
    layout), and the Pallas kernel stays in the program."""
    hlo = _compile(lambda x: offload_aggregate(x, agg=agg,
                                               result_on_host=True),
                   one_chip, ((Q2_RECORDS,), F32))
    layout = re.search(r"entry_computation_layout=\{\((.*?)\)->(.*?)\}\}",
                       hlo)
    assert layout and "S(5)" in layout.group(2)
    assert "copy-done" in hlo


@pytest.mark.parametrize("T,C,window,stride", [
    (300, 8, 180, 60),      # Q1 on a short series: fewer than 8 outputs
    (3600, 8, 180, 60),     # Q1 over an hour
    (512, 1, 128, 64),      # KernelCalibrator's window_agg dry-run
])
def test_window_agg_compiles(one_chip, T, C, window, stride):
    _compile(lambda x: window_aggregate(x, agg="max", window=window,
                                        stride=stride),
             one_chip, ((T, C), F32))


@pytest.mark.parametrize("B,L,H,P,N,chunk", [
    (1, 128, 2, 64, 16, 64),        # KernelCalibrator's ssd_scan dry-run
    (1, 2048, 64, 64, 128, 256),    # mamba2-1.3b widths
])
def test_ssd_scan_compiles(one_chip, B, L, H, P, N, chunk):
    _compile(lambda *a: ssd_scan(*a, chunk=chunk), one_chip,
             ((B, L, H, P), F32), ((B, L, H), F32), ((H,), F32),
             ((B, L, 1, N), F32), ((B, L, 1, N), F32))


def test_flash_attention_compiles_smollm(one_chip):
    """smollm-135m widths: 9 query heads over 3 KV heads, d 64, bf16."""
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
             one_chip, ((1, 2048, 9, 64), BF16), ((1, 2048, 3, 64), BF16),
             ((1, 2048, 3, 64), BF16))
