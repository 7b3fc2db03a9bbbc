"""Every ``pallas_call`` in ``ops/`` must get through JAX's own Pallas-TPU
lowering at the shapes ``chip_smoke.py`` runs on the chip — bf16 and f32,
forward and gradient. ``interpret=True`` (the CPU test path everywhere
else) skips that lowering and its block-shape rules, which is how two
kernels with illegal SMEM block specs went unnoticed until a chip ran
them. Lowering for ``("tpu",)`` needs no TPU; what Mosaic then makes of
the kernel only ``tests_tpu/`` and the smoke can say.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops.diff_attention import diff_decode_attention_pallas
from deeplearning4j_tpu.ops.eva_attention import eva_decode_attention_pallas
from deeplearning4j_tpu.ops.flash_attention import (
    flash_attention, flash_decode_attention, flash_masked_cache_write)
from deeplearning4j_tpu.ops.grouped_matmul import _gmm, _tiling
from deeplearning4j_tpu.ops.mla_attention import mla_decode_attention_pallas
from deeplearning4j_tpu.ops.selective_scan import selective_scan_pallas

DTYPES = [jnp.bfloat16, jnp.float32]


def _kernels(fn, *args):
    """Names of the Pallas TPU kernels in ``fn``'s program lowered for TPU
    (with x64 off, as on the chip: tests/conftest.py turns it on, and
    Mosaic has no float64)."""
    with jax.enable_x64(False):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return sorted(re.findall(r'kernel_name = "([^"]+)"', text))


def _spec(*shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,h,t,d,causal", [
    (4, 12, 512, 64, False),    # the BERT-base 4 x 512 step
    (2, 12, 1024, 64, True),    # GPT-2-small training length, causal+mask
    (2, 12, 600, 64, True),     # no block size divides it
])
def test_flash_forward_and_backward_lower(dtype, b, h, t, d, causal):
    x = _spec(b, h, t, d, dtype=dtype)
    mask = _spec(b, t, dtype=jnp.float32) if causal else None

    def loss(q, k, v, mask):
        return jnp.sum(flash_attention(q, k, v, mask=mask, causal=causal,
                                       interpret=False).astype(jnp.float32))

    assert _kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x, mask) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def _decode_kernels(b, h, h_kv, L, d, dtype, write):
    """Kernel names of a decode call lowered for TPU; ``write``: the call
    that also writes the step's K and V entries into the caches."""
    def call(q, k, v, p, kn, vn, m):
        if not write:
            return flash_decode_attention(q, k, v, p, interpret=False)
        return flash_decode_attention(q, k, v, p, interpret=False,
                                      new=(kn, vn), write_mask=m)

    kv, new = _spec(b, h_kv, L, d, dtype=dtype), _spec(b, h_kv, 1, d,
                                                       dtype=dtype)
    return _kernels(call, _spec(b, h, 1, d, dtype=dtype), kv, kv,
                    _spec(b, dtype=jnp.int32), new, new,
                    _spec(b, dtype=jnp.bool_))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,L,write", [
    pytest.param(8, 1024, False, id="8-1024"),
    pytest.param(8, 600, False, id="8-600"),
    # the serve cell's own step: 128 slots; and with the step's write
    pytest.param(128, 1024, False, id="128-1024"),
    pytest.param(128, 1024, True, id="128-1024-write"),
])
def test_flash_decode_lowers(dtype, b, L, write):
    assert _decode_kernels(b, 12, 12, L, 64, dtype, write) == ["flash_decode"]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("h, h_kv, write", [
    pytest.param(32, 8, False, id="32-8"), pytest.param(4, 2, False, id="4-2"),
    pytest.param(32, 8, True, id="32-8-write"),
])
def test_grouped_flash_decode_lowers(dtype, h, h_kv, write):
    """K and V of fewer heads than the queries (LFM2's 32 over 8 at the
    cell's 128 rows x 6,144 positions): the group's queries are rows of
    one product; with the step's write, the caches aliased to outputs."""
    assert _decode_kernels(128, h, h_kv, 6144, 64, dtype, write) == [
        "flash_decode"]


@pytest.mark.parametrize("dtype", DTYPES)
def test_eva_decode_lowers(dtype):
    """EvaByte's step at its published widths: 16 rows, 32 heads of 128,
    2,048 summaries and 2,048 singletons a plane."""
    b, h, d, n_sum, w = 16, 32, 128, 2048, 2048
    plane = _spec(b, h, n_sum + w, d, dtype=dtype)
    names = _kernels(
        lambda q, k, v, s, n: eva_decode_attention_pallas(
            q, k, v, s, n, n_sum, interpret=False),
        _spec(b, h, 1, d, dtype=dtype), plane, plane,
        _spec(b, dtype=jnp.int32), _spec(b, dtype=jnp.int32))
    assert names == ["eva_decode"]


@pytest.mark.parametrize("dtype", DTYPES)
def test_mla_decode_lowers(dtype):
    """LongCat-Flash's step at its published widths: 128 rows, 64 heads
    over one plane of 2,560 entries of 512 + 64 numbers."""
    b, h, L, rank, rope = 128, 64, 2560, 512, 64
    names = _kernels(
        lambda q, plane, n: mla_decode_attention_pallas(
            q, plane, n, rank, 192 ** -0.5, interpret=False),
        _spec(b, h, rank + rope, dtype=dtype),
        _spec(b, 1, L, rank + rope, dtype=dtype), _spec(b, dtype=jnp.int32))
    assert names == ["mla_decode"]


@pytest.mark.parametrize("dtype", DTYPES)
def test_mla_verify_lowers(dtype):
    """openPangu-Ultra-MoE's verify window at its published widths: 128
    rows, 2 positions of 128 heads over one plane of 2,560 entries of 512 +
    64 numbers."""
    b, tq, h, L, rank, rope = 128, 2, 128, 2560, 512, 64
    names = _kernels(
        lambda q, plane, n: mla_decode_attention_pallas(
            q, plane, n, rank, 192 ** -0.5, interpret=False, tq=tq),
        _spec(b, tq * h, rank + rope, dtype=dtype),
        _spec(b, 1, L, rank + rope, dtype=dtype), _spec(b, dtype=jnp.int32))
    assert names == ["mla_verify"]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("L,name", [(10240, "diff_decode"),
                                    (512, "diff_decode_window")])
def test_diff_decode_lowers(dtype, L, name):
    """Phi-4-mini-flash's step at its published widths: 64 rows, 40 query
    heads over 20 K/V heads of 64 in pairs, the shared cache of 10,240
    entries and a window's ring of 512."""
    b, hq, hk, d = 64, 40, 20, 64
    plane = _spec(b, hk, L, d, dtype=dtype)
    names = _kernels(
        lambda q, k, v, n, lam: diff_decode_attention_pallas(
            q, k, v, n, lam, name=name, interpret=False),
        _spec(b, hq, d, dtype=dtype), plane, plane,
        _spec(b, dtype=jnp.int32), _spec(dtype=jnp.float32))
    assert names == [name]


@pytest.mark.parametrize("t", [8192, 300])
def test_selective_scan_lowers(t):
    """Phi-4-mini-flash's Mamba prefill at its published widths: one row,
    d_inner 5,120, d_state 16; 8,192 positions, and a length that no
    block divides."""
    di, n = 5120, 16
    f32 = jnp.float32
    names = _kernels(
        lambda x, d, a, b, c, s: selective_scan_pallas(x, d, a, b, c, s,
                                                       interpret=False),
        _spec(1, t, di, dtype=f32), _spec(1, t, di, dtype=f32),
        _spec(di, n, dtype=f32), _spec(1, t, n, dtype=f32),
        _spec(1, t, n, dtype=f32), _spec(1, n, di, dtype=f32))
    assert names == ["selective_scan"]


@pytest.mark.parametrize("shape", [(8, 12, 1024, 64), (8, 12, 1024),
                                   (8, 12, 600, 64), (16, 32, 4096, 128),
                                   (128, 1, 2560, 576)])
@pytest.mark.parametrize("dtype", DTYPES + [jnp.int8])
def test_kv_cache_write_lowers(dtype, shape):
    new = shape[:2] + (1,) + shape[3:]
    names = _kernels(
        lambda c, n, p, m: flash_masked_cache_write(c, n, p, m,
                                                    interpret=False),
        _spec(*shape, dtype=dtype), _spec(*new, dtype=dtype),
        _spec(shape[0], dtype=jnp.int32), _spec(shape[0], dtype=jnp.bool_))
    assert names == ["kv_cache_write"]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n,d,e,h,cap", [
    (16384, 768, 8, 1536, 2560),   # ROADMAP S2, first expert matmul
    (16384, 1536, 8, 768, None),   # second one, no capacity bound
    (300, 16, 4, 32, 100),         # widths no tile divides
])
def test_grouped_matmul_forward_and_grad_lower(dtype, n, d, e, h, cap):
    m_pad, bm = _tiling(n, cap, 128, jnp.dtype(dtype).itemsize)

    def loss(lhs, rhs, sizes):  # the Pallas path, as "auto" picks on a TPU
        return jnp.sum(_gmm(lhs, rhs, sizes, m_pad, bm, True,
                            False).astype(jnp.float32))

    names = _kernels(jax.value_and_grad(loss, argnums=(0, 1)),
                     _spec(n, d, dtype=dtype), _spec(e, d, h, dtype=dtype),
                     _spec(e, dtype=jnp.int32))
    assert names == ["grouped_matmul", "grouped_matmul"]  # forward + dgrad
