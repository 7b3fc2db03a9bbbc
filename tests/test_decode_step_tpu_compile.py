"""The serve cell's decode step, its largest prefill (which installs its
own row and first token since ISSUE 37) and the install of a handed-over
row, compiled at GPT-2-small's
widths (128 slots x 1,024 positions, 12 heads of 64; two blocks instead of
twelve) for a described TPU v5e: no chip is needed, nothing runs. What only
the chip's compiler shows (ISSUE 27): the chip keeps a ``[b, h, L, d]`` cache
position-minor, so a kernel that wants it another way costs a transpose of
every plane every step, and a carry that is not donated costs a copy of
every plane. Both must stay away: every plane aliased, no copy of a plane,
no select over one.

The same reading for EvaByte's step at its published widths (ISSUE 29: 16
slots, 32 heads of 128, two planes of 2,048 summaries + 2,048 singletons a
layer; two blocks instead of eight): the mixer declares its planes, so the
whole carry is aliased, and a plane whose head dimension fills the lanes
lies position-major, so both of the step's kernels take it as it lies.

The same again for every cell's largest prefill bucket (ISSUE 37: the
admission is one program that takes the donated batch carry): every plane
and rolling state aliased, no copy, select or transpose of a plane, and the
temporaries, which now hold the fresh row, beside weights and planes under
the chip's 16.9 GB.

The topology is described inside a fixture (never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file); the file's tests are skipped where it cannot be described.
"""

import os
import re

import numpy as np
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


def _compile_step_and_install(model, params, slots, max_len, one_chip,
                              bucket):
    """name -> (compiled text, memory analysis, bytes of the 4-D leaves of
    the carry) of an engine's decode step, its prefill of ``bucket`` tokens
    and its install of a handed-over row at ``slots`` rows, lowered from
    shapes for the described chip."""
    from deeplearning4j_tpu.generate.session import ROW_SPEC_WORDS
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.parallel.decode import DecodeEngine

    def spec(a, shape=None):
        return jax.ShapeDtypeStruct(a.shape if shape is None else shape,
                                    a.dtype, sharding=one_chip)

    tm = jax.tree_util.tree_map
    out = {}
    eng = DecodeEngine(model, max_len=max_len, slots=1,
                       registry=MetricsRegistry())
    # the token vector (a self-speculating engine's rows' image) and the
    # host's image of the rows, at ``slots`` rows
    toks = spec(eng._toks, (slots,) + eng._toks.shape[1:])
    rows = len(eng._step_args(np.ones((1,), bool))[1])
    try:
        carry = tm(lambda a: spec(a, (slots,) + a.shape[1:]), eng._carry)
        planes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree_util.tree_leaves(carry) if l.ndim == 4)
        # the program's own switch takes its TPU branches while lowering
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            lowered = {
                "decode_step": eng._decode_step_fn().lower(
                    tm(spec, params), tm(spec, model.state), carry,
                    # the step before's tokens, the host's image of the rows
                    toks, jax.ShapeDtypeStruct(
                        (rows, slots), jnp.int32, sharding=one_chip)),
                # the step's tokens again, then the admission's one array
                f"prefill_{bucket}": eng._prefill_fn(bucket).lower(
                    tm(spec, params), tm(spec, model.state), carry,
                    toks, jax.ShapeDtypeStruct(
                        (ROW_SPEC_WORDS + bucket,), jnp.int32,
                        sharding=one_chip)),
                "install_row": eng._write_row_fn().lower(
                    carry, tm(spec, eng._row_template),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)),
            }
        for name, low in lowered.items():
            c = low.compile()
            out[name] = (c.as_text(), c.memory_analysis(), planes)
    finally:
        eng.shutdown(drain=False)
    return out


@pytest.fixture(scope="module")
def programs(one_chip):
    from deeplearning4j_tpu.model.zoo import TransformerLM

    with jax.enable_x64(False):  # as on the chip; Mosaic has no float64
        model = TransformerLM(vocab_size=50257, hidden=HIDDEN, n_layers=2,
                              n_heads=HEADS, ffn_size=3072, max_len=MAX_LEN,
                              dtype="bfloat16").init()
        return _compile_step_and_install(model, model.params, SLOTS, MAX_LEN,
                                         one_chip, 512)


@pytest.mark.parametrize("name", ["decode_step", "install_row",
                                  "prefill_512"])
def test_every_plane_is_updated_where_it_lies(programs, name):
    text, ma, planes = programs[name]
    assert ma.alias_size_in_bytes >= planes, (ma.alias_size_in_bytes, planes)
    # no second carry among the temporaries (one plane is 201 MB; the
    # cell's largest bucket reads 56 MB, its fresh row of 6 MB among them)
    assert ma.temp_size_in_bytes < planes // 8, ma.temp_size_in_bytes
    lines = text.splitlines()
    copies = [l[:160] for l in lines
              if re.search(rf"= {PLANE}\S* copy\(", l)]
    assert not copies, copies[:3]
    selects = [l[:160] for l in lines
               if re.search(rf"= {PLANE}\S* select\(", l)]
    assert not selects, selects[:3]


def test_the_step_holds_its_kernels_and_no_loop(programs):
    """Two blocks: two calls of the decode kernel, which writes the step's
    K and V entries itself (no call of the separate cache write), and no
    `%while` of one small update a row in its place."""
    text = programs["decode_step"][0]
    assert text.count("tpu_custom_call") >= 2
    assert "flash_decode" in text and "kv_cache_write" not in text
    assert not re.search(r" while\(", text)
    # the new entries reach the kernel as rows: an entry laid out as a
    # column [.., 64, 1] lies padded to 128 lanes, 12.6 MB a plane a step
    assert not re.search(rf"bf16\[{SLOTS},{HEADS},{HIDDEN // HEADS},1\]",
                         text)


# what PERF.md section 4 gives the step for temporaries (GB 0.049). At this
# file's two blocks the parent of ISSUE 35 read 30,271,488 (two stable sorts
# of f32[128, 50257], each beside an index plane) and the branching sampler
# reads 38,231,552 (its arms take the logits as float32, 25.7 MB, so that no
# bfloat16 rounding becomes real at the branch); at the cell's twelve blocks
# 57,746,432 and 57,972,224
STEP_TEMP_ROOM = 49_000_000


def _computations(text):
    """name -> lines of each computation of a compiled module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def test_the_step_sorts_once_and_only_inside_a_branch(programs):
    """The sampler branches on its batch's specs INSIDE the step: the one
    sort over the vocabulary is in a computation that only a
    ``conditional``'s branch reaches, so an all-greedy step never runs it;
    the arms hand back tokens alone and share their temporaries; and what
    they are handed is float32 (ISSUE 35)."""
    text, ma, _ = programs["decode_step"]
    comps = _computations(text)
    called = {name: {c for l in lines for c in re.findall(r"%([\w.\-]+)", l)
                     if c in comps}
              for name, lines in comps.items()}
    branches = {c.strip().lstrip("%")
                for l in text.splitlines() if " conditional(" in l
                for group in re.findall(r"branch_computations=\{([^}]*)\}", l)
                for c in group.split(",")}
    assert len(branches) == 3, branches  # argmax, sample, sort
    reach, todo = set(), list(branches)
    while todo:
        c = todo.pop()
        if c not in reach:
            reach.add(c)
            todo.extend(called[c])
    sorts = [name for name, lines in comps.items()
             for l in lines if re.search(r" sort\(", l)]
    assert len(sorts) == 1 and sorts[0] in reach, sorts
    (cond,) = [l for l in text.splitlines() if " conditional(" in l]
    assert re.search(r"= \(s32\[128\]\S*\) conditional\(", cond), cond[:200]
    operands = re.findall(r"%(tuple[\w.\-]*)", cond.split("conditional(")[1])
    handed = [l for l in text.splitlines()
              if re.match(rf"\s*%({'|'.join(map(re.escape, operands))}) = ", l)]
    assert handed and not [l[:200] for l in handed
                           if "bf16[128,50257]" in l], handed
    assert ma.temp_size_in_bytes <= STEP_TEMP_ROOM, ma.temp_size_in_bytes


# ------------------------------------------------------------------ EvaByte
EVA_SLOTS, EVA_MAX_LEN = 16, 32768
EVA_PLANE = rf"bf16\[{EVA_SLOTS},32,4096,128\]"


@pytest.fixture(scope="module")
def eva_programs(one_chip):
    from deeplearning4j_tpu.model.zoo import EvaByteLM
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

    tm = jax.tree_util.tree_map
    with jax.enable_x64(False):
        model = MultiLayerNetwork(EvaByteLM(
            vocab_size=320, hidden=4096, n_layers=2, n_heads=32,
            ffn_size=11008, window=2048, chunk=16, n_pred_heads=8,
            max_len=EVA_MAX_LEN, dtype="bfloat16").conf())
        # shapes only: the published widths' weights are never made here
        params = jax.eval_shape(lambda: model.init().params)
        model.params = tm(lambda a: jnp.zeros((), a.dtype), params)
        model._initialized = True
        model.state = {n: {} for n in model.layer_names()}
        model._persistent_keys = {n: () for n in model.layer_names()}
        return _compile_step_and_install(model, params, EVA_SLOTS,
                                         EVA_MAX_LEN, one_chip, EVA_MAX_LEN)


# what 3.26 GB of weights and 8.59 GB of state leave on a chip of 16.9 GB,
# halved. The 32,768 bucket reads 0.59 GB at this file's two blocks, their
# fresh row of 0.13 GB inside (67 MB a layer and plane pair: at the cell's
# eight blocks a row is 0.54 GB, which left the program as a buffer before
# ISSUE 37)
EVA_ROOM = 2_500_000_000


@pytest.mark.parametrize("name", ["decode_step", "install_row",
                                  "prefill_32768"])
def test_evabyte_carry_is_aliased_whole_and_no_plane_is_copied(eva_programs,
                                                               name):
    text, ma, planes = eva_programs[name]
    # two layers' planes, 2.147 GB, and the open chunks' entries beside them
    assert planes >= 2 * 2 * EVA_SLOTS * 32 * 4096 * 128 * 2
    assert ma.alias_size_in_bytes >= planes, (ma.alias_size_in_bytes, planes)
    room = EVA_ROOM if name == "prefill_32768" else planes // 8
    assert ma.temp_size_in_bytes < room, ma.temp_size_in_bytes
    lines = text.splitlines()
    for op in ("copy", "select", "transpose"):
        hits = [l[:160] for l in lines
                if re.search(rf"= {EVA_PLANE}\S* {op}\(", l)]
        assert not hits, hits[:3]


def test_evabyte_step_holds_its_kernels_and_no_loop(eva_programs):
    """Two blocks: two calls of ``eva_decode`` and eight in-place writes (a
    singleton and a summary into each of a block's two planes)."""
    text = eva_programs["decode_step"][0]
    assert text.count("tpu_custom_call") >= 10
    assert "eva_decode" in text and "kv_cache_write" in text
    assert not re.search(r" while\(", text)


# ------------------------------------------------------------------ LongCat
LC_SLOTS, LC_MAX_LEN, LC_HEADS, LC_WIDE = 128, 2560, 64, 576
LC_PLANE = rf"bf16\[{LC_SLOTS},1,{LC_MAX_LEN},{LC_WIDE}\]"
# what the cell's memory reckoning leaves beside 10.35 GB of weights and
# 3.02 GB of latent planes on a chip of 16.9 GB (ISSUE 34)
LC_ROOM = 3_500_000_000


@pytest.fixture(scope="module")
def longcat_programs(one_chip):
    """The latent-attention cell's step, install and largest prefill (the
    1,024 bucket) at LongCat-Flash's published widths (ISSUE 34: 128 slots x
    2,560 positions, 64 heads over one 576-wide latent plane an attention
    block, 16 held experts of 512 beside 256 zero-compute ones; two double
    layers instead of four), shapes only."""
    from deeplearning4j_tpu.model.zoo import LongCatFlashLM
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

    tm = jax.tree_util.tree_map
    with jax.enable_x64(False):
        model = MultiLayerNetwork(LongCatFlashLM(
            vocab_size=16384, hidden=6144, n_layers=2, n_heads=LC_HEADS,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            q_lora_rank=1536, kv_lora_rank=512, ffn_size=12288,
            expert_ffn_size=2048, n_routed_experts=512, zero_expert_num=256,
            n_held_experts=16, moe_topk=12, routed_scaling_factor=6.0,
            dtype="bfloat16").conf())
        params = jax.eval_shape(lambda: model.init().params)
        model.params = tm(lambda a: jnp.zeros((), a.dtype), params)
        model._initialized = True
        model.state = jax.eval_shape(lambda: model.init().state)
        model._persistent_keys = {n: () for n in model.layer_names()}
        return _compile_step_and_install(model, params, LC_SLOTS, LC_MAX_LEN,
                                         one_chip, 1024)


@pytest.mark.parametrize("name", ["decode_step", "install_row",
                                  "prefill_1024"])
def test_longcat_latent_planes_are_aliased_and_none_is_copied(
        longcat_programs, name):
    text, ma, planes = longcat_programs[name]
    # two layers of two planes: 4 x 128 x 2,560 x 576 x 2 B = 1.51 GB
    assert planes == 4 * LC_SLOTS * LC_MAX_LEN * LC_WIDE * 2
    assert ma.alias_size_in_bytes >= planes, (ma.alias_size_in_bytes, planes)
    lines = text.splitlines()
    for op in ("copy", "select", "transpose"):
        hits = [l[:160] for l in lines
                if re.search(rf"= {LC_PLANE}\S* {op}\(", l)]
        assert not hits, hits[:3]
    # the step attends the latent itself: no key or value a head and position
    wide = [l[:160] for l in lines if re.search(
        rf"\[{LC_SLOTS},{LC_HEADS},{LC_MAX_LEN},\d+\]", l)]
    assert not wide, wide[:3]


@pytest.mark.parametrize("name", ["decode_step", "prefill_1024"])
def test_longcat_temporaries_fit_beside_weights_and_planes(longcat_programs,
                                                           name):
    ma = longcat_programs[name][1]
    assert ma.temp_size_in_bytes < LC_ROOM, ma.temp_size_in_bytes


# --------------------------------------------------------------------- LFM2
LF_SLOTS, LF_MAX_LEN, LF_HEADS, LF_KV = 128, 6144, 32, 8
LF_PLANE = rf"bf16\[{LF_SLOTS},{LF_KV},{LF_MAX_LEN},64\]"
LF_STATE = rf"bf16\[{LF_SLOTS},2,2048\]"
# what the cell's memory reckoning leaves beside 9.33 GB of weights and
# 4.83 GB of K/V planes on a chip of 16.9 GB (ISSUE 36)
LF_ROOM = 2_700_000_000


@pytest.fixture(scope="module")
def lfm2_programs(one_chip):
    """The LFM2 cell's step, install and largest prefill (the 4,096 bucket)
    at LFM2-8B-A1B's published widths (ISSUE 36: 128 slots x 6,144
    positions, 32 query heads over a K/V pair of 8 heads of 64, rolling
    states of two columns of 2,048, 32 experts of 1,792 all held; 2 dense +
    4 expert layers instead of 2 + 12), shapes only."""
    from deeplearning4j_tpu.model.zoo import Lfm2MoeLM
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

    tm = jax.tree_util.tree_map
    with jax.enable_x64(False):
        model = MultiLayerNetwork(Lfm2MoeLM(
            vocab_size=65536, hidden=2048,
            layer_types=("conv", "conv", "full_attention", "conv", "conv",
                         "conv"),
            n_dense_layers=2, n_heads=LF_HEADS, n_kv_heads=LF_KV,
            ffn_size=7168, expert_ffn_size=1792, n_experts=32, top_k=4,
            dtype="bfloat16").conf())
        params = jax.eval_shape(lambda: model.init().params)
        model.params = tm(lambda a: jnp.zeros((), a.dtype), params)
        model._initialized = True
        model.state = {n: {} for n in model.layer_names()}
        model._persistent_keys = {n: () for n in model.layer_names()}
        return _compile_step_and_install(model, params, LF_SLOTS, LF_MAX_LEN,
                                         one_chip, 4096)


@pytest.mark.parametrize("name", ["decode_step", "install_row",
                                  "prefill_4096"])
def test_lfm2_planes_and_rolling_states_are_aliased_and_no_plane_is_copied(
        lfm2_programs, name):
    text, ma, planes = lfm2_programs[name]
    # one attention layer of the six: a K and a V plane of 8 heads, 1.61 GB
    assert planes == 2 * LF_SLOTS * LF_KV * LF_MAX_LEN * 64 * 2
    states = 5 * LF_SLOTS * 2 * 2048 * 2       # five convolutions' columns
    assert ma.alias_size_in_bytes >= planes + states, ma.alias_size_in_bytes
    lines = text.splitlines()
    for op in ("copy", "select", "transpose"):
        hits = [l[:160] for l in lines
                if re.search(rf"= {LF_PLANE}\S* {op}\(", l)]
        assert not hits, hits[:3]
    # the cache stays at 8 heads: nothing repeats it to the queries' 32, and
    # no float32 scores [128, 32, 6144] stand outside the kernel
    wide = [l[:160] for l in lines if re.search(
        rf"\[{LF_SLOTS},{LF_HEADS},(\d+,)?{LF_MAX_LEN}(,\d+)?\]", l)]
    assert not wide, wide[:3]


def test_lfm2_step_holds_its_kernels_and_no_loop(lfm2_programs):
    """One attention layer: one call of the grouped decode kernel, which
    writes the step's K and V entries itself; the rolling states are plain
    slices and selects."""
    text = lfm2_programs["decode_step"][0]
    assert text.count("tpu_custom_call") >= 1
    assert "flash_decode" in text and "kv_cache_write" not in text
    assert not re.search(r" while\(", text)
    assert re.search(LF_STATE, text)


@pytest.mark.parametrize("name", ["decode_step", "prefill_4096"])
def test_lfm2_temporaries_fit_beside_weights_and_planes(lfm2_programs, name):
    ma = lfm2_programs[name][1]
    assert ma.temp_size_in_bytes < LF_ROOM, ma.temp_size_in_bytes


# ------------------------------------------------------- Phi-4-mini-flash
PF_SLOTS, PF_MAX_LEN, PF_HEADS, PF_KV, PF_WINDOW = 64, 10240, 40, 20, 512
PF_PLANE = rf"bf16\[{PF_SLOTS},{PF_KV},{PF_MAX_LEN},64\]"
PF_RING = rf"bf16\[{PF_SLOTS},{PF_KV},{PF_WINDOW},64\]"
PF_SCAN = rf"f32\[{PF_SLOTS},16,5120\]"
# what the cell's memory reckoning leaves beside 7.705 GB of weights and
# 4.904 GB of carry on a chip of 16.9 GB (ISSUE 41)
PF_ROOM = 3_500_000_000


@pytest.fixture(scope="module")
def phi4f_programs(one_chip):
    """The Phi-4-mini-flash cell's step, install and largest prefill (the
    8,192 bucket) at the published widths (ISSUE 41: 64 slots x 10,240
    positions, 40 query heads over 20 K/V heads of 64, rings of 512, Mamba
    states of 16 x 5,120; 8 layers instead of 32, by the same rule: Mamba,
    window, Mamba, window | the memory Mamba, the full layer, a GMU, a
    cross layer), shapes only."""
    from deeplearning4j_tpu.model.zoo import Phi4FlashLM
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

    tm = jax.tree_util.tree_map
    with jax.enable_x64(False):
        model = MultiLayerNetwork(Phi4FlashLM(
            vocab_size=200064, hidden=2560, n_layers=8, mb_per_layer=2,
            n_heads=PF_HEADS, n_kv_heads=PF_KV, ffn_size=10240,
            sliding_window=PF_WINDOW, d_inner=5120, d_state=16, d_conv=4,
            dt_rank=160, dtype="bfloat16").conf())
        params = jax.eval_shape(lambda: model.init().params)
        model.params = tm(lambda a: jnp.zeros((), a.dtype), params)
        model._initialized = True
        model.state = {n: {} for n in model.layer_names()}
        model._persistent_keys = {n: () for n in model.layer_names()}
        return _compile_step_and_install(model, params, PF_SLOTS, PF_MAX_LEN,
                                         one_chip, 8192)


@pytest.mark.parametrize("name", ["decode_step", "install_row",
                                  "prefill_8192"])
def test_phi4f_cache_rings_and_scans_are_aliased_and_no_plane_is_copied(
        phi4f_programs, name):
    text, ma, planes = phi4f_programs[name]
    # the one full-length cache and two rings: a K and a V plane each
    assert planes == 2 * PF_SLOTS * PF_KV * 64 * 2 * (PF_MAX_LEN
                                                     + 2 * PF_WINDOW)
    scans = 3 * PF_SLOTS * 16 * 5120 * 4          # three Mamba layers' states
    assert ma.alias_size_in_bytes >= planes + scans, ma.alias_size_in_bytes
    lines = text.splitlines()
    for plane in (PF_PLANE, PF_RING):
        for op in ("copy", "select", "transpose"):
            hits = [l[:160] for l in lines
                    if re.search(rf"= {plane}\S* {op}\(", l)]
            assert not hits, hits[:3]
    # the cache stays at 20 heads: nothing repeats it to the queries' 40,
    # and no float32 scores over 10,240 entries stand outside the kernel
    wide = [l[:160] for l in lines if re.search(
        rf"= (f32\[{PF_SLOTS},({PF_HEADS}|{PF_KV}|10),(\d+,)?{PF_MAX_LEN}\b|"
        rf"bf16\[{PF_SLOTS},{PF_HEADS},{PF_MAX_LEN},)", l)]
    assert not wide, wide[:3]


def test_phi4f_step_holds_both_kernels_and_no_loop(phi4f_programs):
    """Two window layers and two readers of the one cache: two calls of
    ``diff_decode_window`` and two of ``diff_decode``, an in-place write of
    a K and a V entry for each of the three layers that own planes; the
    scans are one position, no loop."""
    text = phi4f_programs["decode_step"][0]
    assert text.count("tpu_custom_call") >= 10
    for kernel in ("diff_decode_window", "diff_decode", "kv_cache_write"):
        assert kernel in text, kernel
    assert not re.search(r" while\(", text)
    assert re.search(PF_SCAN, text)


@pytest.mark.parametrize("name", ["decode_step", "prefill_8192"])
def test_phi4f_temporaries_fit_beside_weights_and_carry(phi4f_programs,
                                                        name):
    ma = phi4f_programs[name][1]
    assert ma.temp_size_in_bytes < PF_ROOM, ma.temp_size_in_bytes


# ------------------------------------------------------------------- Pangu
PG_SLOTS, PG_MAX_LEN, PG_HEADS, PG_WIDE = 128, 2560, 128, 576
PG_PLANE = rf"bf16\[{PG_SLOTS},1,{PG_MAX_LEN},{PG_WIDE}\]"
# what the cell's reckoning leaves beside 12.08 GB of weights and 2.26 GB of
# latent planes on a chip of 16.9 GB (PERF.md section 4)
PG_ROOM = 2_500_000_000


@pytest.fixture(scope="module")
def pangu_programs(one_chip):
    """The self-speculating cell's step (verify at two positions, commit,
    MTP draft), install and largest prefill (the 1,024 bucket, the MTP
    module over the prompt too) at openPangu-Ultra-MoE's published widths
    (the cell's: 128 slots x 2,560 positions, 128 heads over one 576-wide
    latent plane a layer, 16 held experts of 256 and a shared one; one
    dense and one expert layer instead of 1 + 4, and the MTP module),
    shapes only."""
    from deeplearning4j_tpu.model.zoo import PanguUltraMoeLM
    from deeplearning4j_tpu.nn.sequential import MultiLayerNetwork

    tm = jax.tree_util.tree_map
    with jax.enable_x64(False):
        model = MultiLayerNetwork(PanguUltraMoeLM(
            vocab_size=19200, hidden=7680, n_layers=2, n_dense_layers=1,
            n_heads=PG_HEADS, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, q_lora_rank=1536, kv_lora_rank=512,
            ffn_size=18432, expert_ffn_size=2048, n_routed_experts=256,
            n_held_experts=16, n_shared_experts=1, top_k=8,
            routed_scaling_factor=2.5, expert_rows=256,
            dtype="bfloat16").conf())
        params = jax.eval_shape(lambda: model.init().params)
        model.params = tm(lambda a: jnp.zeros((), a.dtype), params)
        model._initialized = True
        model.state = jax.eval_shape(lambda: model.init().state)
        model._persistent_keys = {n: () for n in model.layer_names()}
        return _compile_step_and_install(model, params, PG_SLOTS, PG_MAX_LEN,
                                         one_chip, 1024)


@pytest.mark.parametrize("name", ["decode_step", "install_row",
                                  "prefill_1024"])
def test_pangu_latent_planes_are_aliased_and_none_is_copied(pangu_programs,
                                                            name):
    text, ma, planes = pangu_programs[name]
    # two layers and the MTP module: 3 x 128 x 2,560 x 576 x 2 B = 1.13 GB
    assert planes == 3 * PG_SLOTS * PG_MAX_LEN * PG_WIDE * 2
    assert ma.alias_size_in_bytes >= planes, (ma.alias_size_in_bytes, planes)
    lines = text.splitlines()
    for op in ("copy", "select", "transpose"):
        hits = [l[:160] for l in lines
                if re.search(rf"= {PG_PLANE}\S* {op}\(", l)]
        assert not hits, hits[:3]


def test_pangu_step_verifies_every_plane_through_its_kernel(pangu_programs):
    """The step's two positions attend each layer's plane and the MTP
    module's through ``mla_verify``, once a plane, and write their two
    entries by two calls of the in-place one-entry write (a scatter of two
    entries a row compiles to a ``%while`` over the rows); nothing else is
    a kernel, and no loop is left."""
    text = pangu_programs["decode_step"][0]
    names = sorted(re.match(r"\s*%([a-z_]+)", l).group(1)
                   for l in text.splitlines()
                   if " custom-call(" in l and "tpu_custom_call" in l)
    assert names == ["kv_cache_write"] * 6 + ["mla_verify"] * 3, names
    assert not re.search(r" while\(", text)


@pytest.mark.parametrize("name", ["decode_step", "prefill_1024"])
def test_pangu_temporaries_fit_beside_weights_and_planes(pangu_programs,
                                                         name):
    ma = pangu_programs[name][1]
    assert ma.temp_size_in_bytes < PG_ROOM, ma.temp_size_in_bytes
