"""The serve cell's decode step and install, compiled at GPT-2-small's
widths (128 slots x 1,024 positions, 12 heads of 64; two blocks instead of
twelve) for a described TPU v5e: no chip is needed, nothing runs. What only
the chip's compiler shows (ISSUE 27): the chip keeps a ``[b, h, L, d]`` cache
position-minor, so a kernel that wants it another way costs a transpose of
every plane every step, and a carry that is not donated costs a copy of
every plane. Both must stay away: every plane aliased, no copy of a plane,
no select over one.

The topology is described inside a fixture (never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file); the file's tests are skipped where it cannot be described.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp

SLOTS, MAX_LEN, HEADS, HIDDEN = 128, 1024, 12, 768
PLANE = rf"bf16\[{SLOTS},{HEADS},{MAX_LEN},{HIDDEN // HEADS}\]"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def programs(one_chip):
    """name -> (compiled text, memory analysis, bytes of the cache planes)."""
    from deeplearning4j_tpu.model.zoo import TransformerLM
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.parallel.decode import DecodeEngine

    def spec(a, shape=None):
        return jax.ShapeDtypeStruct(a.shape if shape is None else shape,
                                    a.dtype, sharding=one_chip)

    def vec(dtype):
        return jax.ShapeDtypeStruct((SLOTS,), dtype, sharding=one_chip)

    tm = jax.tree_util.tree_map
    out = {}
    with jax.enable_x64(False):  # as on the chip; Mosaic has no float64
        model = TransformerLM(vocab_size=50257, hidden=HIDDEN, n_layers=2,
                              n_heads=HEADS, ffn_size=3072, max_len=MAX_LEN,
                              dtype="bfloat16").init()
        eng = DecodeEngine(model, max_len=MAX_LEN, slots=1,
                           registry=MetricsRegistry())
        try:
            sess = eng.session
            carry = tm(lambda a: spec(a, (SLOTS,) + a.shape[1:]), eng._carry)
            planes = sum(
                l.size * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(carry) if l.ndim == 4)
            # the program's own switch takes its TPU branches while lowering
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                lowered = {
                    "decode_step": eng._decode_step_fn().lower(
                        tm(spec, model.params), tm(spec, model.state), carry,
                        vec(jnp.int32), vec(jnp.bool_), vec(jnp.uint32),
                        vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32),
                        vec(jnp.int32), vec(jnp.float32)),
                    "install_row": eng._write_row_fn().lower(
                        carry, tm(spec, eng._row_template),
                        jax.ShapeDtypeStruct((), jnp.int32,
                                             sharding=one_chip)),
                }
            for name, low in lowered.items():
                c = low.compile()
                out[name] = (c.as_text(), c.memory_analysis(), planes)
        finally:
            eng.shutdown(drain=False)
    return out


@pytest.mark.parametrize("name", ["decode_step", "install_row"])
def test_every_plane_is_updated_where_it_lies(programs, name):
    text, ma, planes = programs[name]
    assert ma.alias_size_in_bytes >= planes, (ma.alias_size_in_bytes, planes)
    # no second carry among the temporaries (one plane is 201 MB)
    assert ma.temp_size_in_bytes < planes // 8, ma.temp_size_in_bytes
    lines = text.splitlines()
    copies = [l[:160] for l in lines
              if re.search(rf"= {PLANE}\S* copy\(", l)]
    assert not copies, copies[:3]
    selects = [l[:160] for l in lines
               if re.search(rf"= {PLANE}\S* select\(", l)]
    assert not selects, selects[:3]


def test_the_step_holds_its_kernels_and_no_loop(programs):
    """Two blocks: two calls of the decode kernel, four of the in-place
    cache write, and no `%while` of one small update a row in its place."""
    text = programs["decode_step"][0]
    assert text.count("tpu_custom_call") >= 6
    assert "flash_decode" in text and "kv_cache_write" in text
    assert not re.search(r" while\(", text)
