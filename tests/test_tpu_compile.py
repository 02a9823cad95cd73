"""The new cell's Pallas kernels compiled for a DESCRIBED v5e at the
cell's real widths — no chip attached, nothing runs, no time or result
is read. What interpret mode cannot see is caught here at no chip time:
a slice the tiling refuses, more VMEM than a kernel may use (the
streaming flash backward at 8,192 x (192 + 128) needed a raised limit,
PR 26).

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file. All such tests live in this one file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


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


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16 = jnp.float32, jnp.bfloat16
#: the cell's KDA call: 1 x 8,192 tokens, 32 heads of 128 x 128
KDA = (1, 8192, 32, 128)


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_kda_chunk_kernels_compile_at_the_cells_shapes(one_chip, which):
    from paddle_tpu.ops.pallas import kda

    seq = [(KDA, F32)] * 4 + [(KDA[:3], F32)]
    if which == "fwd":
        out = _compile(lambda *a: kda._pallas_fwd(*a, kda.CHUNK), one_chip,
                       *seq)
    else:
        states = (1, 32, 8192 // kda.CHUNK, 128, 128)
        out = _compile(lambda *a: kda._pallas_bwd(*a, kda.CHUNK), one_chip,
                       *seq, (states, F32), (KDA, F32))
    assert f"kda_chunk_{which}" in out.as_text()


def test_stream_flash_compiles_at_mlas_widths_and_the_sequence_ceiling(
        one_chip):
    from paddle_tpu.ops.pallas import flash_attention as fa

    def loss(q, k, v):
        return jnp.sum(fa._flash_attention_pallas(
            q, k, v, causal=True).astype(F32))

    out = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                   ((1, 8192, 32, 192), BF16), ((1, 8192, 32, 192), BF16),
                   ((1, 8192, 32, 128), BF16))
    text = out.as_text()
    assert "flash_attention_stream_fwd" in text
    assert "flash_attention_stream_bwd" in text


def test_fused_xent_compiles_at_hidden_2304_on_the_top_rung(one_chip):
    from paddle_tpu.ops.pallas import fused_xent as fx

    n, hd, v = 8192, 2304, 20480
    bn, bv = fx._pick_blocks(n, hd, v)

    def loss(h, w, b, lab):
        s, c = fx._fused_xent_sums(h, w, b, lab, -100, (n,))
        return s / c

    out = _compile(jax.grad(loss, argnums=(0, 1)), one_chip,
                   ((n, hd), F32), ((v, hd), F32), ((v,), F32),
                   ((n,), jnp.int32))
    assert (bn, bv) == (256, 256)
    assert "fused_xent_bwd" in out.as_text()
