"""The cell ``phi4f-reasoning-closed64``: its configuration holds the
catalog's row and the issue's arithmetic, and its comparison has been shown
to fail, at rehearsal size (hidden 64, 8 layers by the published rule, 8
query and 4 K/V heads of 8, window 16, d_inner 128, vocabulary 512,
float32), on the pattern of ``test_lfm2_cell.py``:

* the control: the plain reference in the program's place, computed in
  ``float8_e4m3fn`` (the nearest precision below the configuration's), and
  one served token altered;
* the cell's own faults, planted in the PROGRAM under a rehearsed run: the
  window's band unbounded; lambda's second map dropped; the GMUs reading
  the Mamba layer's output after its gate; the cross layers attending keys
  and values of their own input instead of the full layer's cache; ``dt``
  not masked at the prompt's padding.

Each must fail one of the limits a rehearsed run is held to; the sound
rehearsal passes both.
"""

import json
import math
import os

import pytest

from benchmarks import limits as limits_tool
from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "phi4f-reasoning-closed64"
_CELL = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
LIMITS = _CELL["limits"] | _CELL["rehearse"]["cell"]["limits"]


def _rehearse(seed="3000000051"):
    return bench_run.main(["--workload", CELL, "--seed", seed, "--seconds",
                           "1", "--trace", "0", "--rehearse"])


def test_sound_rehearsal_is_correct():
    line = _rehearse()
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(LIMITS)
    for name, check in line["checks"].items():
        assert check["value"] <= check["limit"], name


def test_control_in_lower_precision_fails_the_cell():
    (row,) = limits_tool.main([
        "--workload", CELL, "--seeds", "3000000052", "--control", "1",
        "--seconds", "2", "--rehearse"])
    assert all(row["program"][k] <= v for k, v in LIMITS.items())
    assert row["control_float8_e4m3fn"]["served_mean_logit_gap"] > \
        LIMITS["served_mean_logit_gap"]
    assert row["fault_one_token_altered"]["served_logit_gap"] > \
        LIMITS["served_logit_gap"]


def _band_unbounded(monkeypatch):
    from deeplearning4j_tpu.model.zoo import phi4_flash

    init = phi4_flash.Phi4FlashLM.__init__

    def faulty(self, *args, **kw):
        init(self, *args, **dict(kw, sliding_window=128))

    monkeypatch.setattr(phi4_flash.Phi4FlashLM, "__init__", faulty)


def _second_map_dropped(monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import DifferentialAttentionLayer

    monkeypatch.setattr(DifferentialAttentionLayer, "_lam",
                        lambda self, params: jnp.zeros((), jnp.float32))


def _gmu_reads_after_the_gate(monkeypatch):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import MambaMixerLayer

    mix = MambaMixerLayer._mix

    def faulty(self, params, state, x, mask, tap):
        out = mix(self, params, state, x, mask, tap)
        if not tap:
            return out
        z = jnp.dot(x, params["Win"])[..., self.d_inner:]
        return out[0], out[1], out[2] * jax.nn.silu(z)

    monkeypatch.setattr(MambaMixerLayer, "_mix", faulty)


def _cross_layers_use_fresh_kv(monkeypatch):
    from deeplearning4j_tpu.nn.layers import DifferentialAttentionLayer
    from deeplearning4j_tpu.nn.layers.attention import _split_heads

    mix = DifferentialAttentionLayer._mix
    full = {}

    def faulty(self, params, state, x, mask, shared):
        if self.kind == "full":
            full.update(Wk=params["Wk"], Wv=params["Wv"])
        elif self.kind == "cross":
            shared = {"k": _split_heads(x @ full["Wk"], self.n_kv_heads),
                      "v": _split_heads(x @ full["Wv"], self.n_kv_heads)}
        return mix(self, params, state, x, mask, shared)

    monkeypatch.setattr(DifferentialAttentionLayer, "_mix", faulty)


def _dt_not_masked_at_padding(monkeypatch):
    from deeplearning4j_tpu.nn.layers import MambaMixerLayer, mamba

    mix = MambaMixerLayer._mix
    conv = mamba.rolling_conv

    def faulty(self, params, state, x, mask, tap):
        if mask is None:
            return mix(self, params, state, x, mask, tap)
        # the convolution hands over at the true length; the scan runs on
        monkeypatch.setattr(mamba, "rolling_conv",
                            lambda xs, st, w, m: conv(xs, st, w, mask))
        try:
            return mix(self, params, state, x, None, tap)
        finally:
            monkeypatch.setattr(mamba, "rolling_conv", conv)

    monkeypatch.setattr(MambaMixerLayer, "_mix", faulty)


@pytest.mark.parametrize("plant", [
    _band_unbounded, _second_map_dropped, _gmu_reads_after_the_gate,
    _cross_layers_use_fresh_kv, _dt_not_masked_at_padding],
    ids=lambda f: f.__name__.strip("_"))
def test_fault_in_the_program_fails_the_cell(monkeypatch, plant):
    plant(monkeypatch)
    line = _rehearse()
    assert line["correct"] is False
    failed = [n for n, c in line["checks"].items()
              if c["value"] is None or c["value"] > c["limit"]]
    assert failed, line["checks"]


def test_the_chips_limits_lie_between_their_readings():
    """Each limit has room above the program's largest reading on the chip
    and under the least of the control or the fault it is there for: the
    widest gap under one altered token, the mean under the fp8 control."""
    read, limits = _CELL["limits_read"], _CELL["limits"]
    program = read["program"]
    assert len(program["seeds"]) >= 4
    for name in ("served_logit_gap", "served_mean_logit_gap"):
        assert 3 * program[name][1] < limits[name], name
    assert 3 * limits["served_logit_gap"] < \
        read["one_token_altered"]["served_logit_gap"][0]
    assert 3 * limits["served_mean_logit_gap"] < \
        read["control_float8_e4m3fn"]["served_mean_logit_gap"][0]


# ------------------------------------------- the configuration and its work
def _family_and_dims():
    from benchmarks.harness import runtime

    config = json.load(open(os.path.join(BENCH, "configs",
                                         "phi4-mini-flash.json")))
    family = runtime.load_family(runtime.family_file(BENCH, config))
    return family, family.dims(config), config


def test_the_model_weighs_what_the_issue_reckoned():
    """ISSUE 41's arithmetic from the family's own tree: each kind of
    block, the embedding, the whole 3.8B; every number of the catalog's
    row under its own key, nothing reduced; the carry of 64 rows."""
    family, d, config = _family_and_dims()
    counts = family.groups(d)

    def block(g):
        return sum(math.prod(shape) for k, (_, shape) in
                   family.leaves(d).items() if k.startswith(g + "_"))

    assert block("sm") == block("mm") == 119_895_040
    assert block("sw") == block("fa") == 98_314_624
    assert block("xg") == 104_867_840
    assert block("xc") == 91_761_024
    assert counts == {"sm": 8, "sw": 8, "xg": 7, "xc": 7}
    total = sum(math.prod(shape) * counts.get(g, 1)
                for g, shape in family.leaves(d).values())
    assert total == 3_852_457_984 == config["arithmetic"]["total"]
    assert math.prod(family.leaves(d)["tok_emb"][1]) == 512_163_840
    row = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}
    assert {k: config[k] for k in row} == row
    assert config["reduced"] == []
    m = config["model"]
    assert (m["hidden"], m["n_layers"], m["n_heads"], m["n_kv_heads"],
            m["ffn_size"], m["sliding_window"], m["vocab_size"]) == (
        2560, 32, 40, 20, 10240, 512, 200064)
    # a row: 9 scans of 358,400 B, 8 rings of 2,621,440 B, 5,120 B a token
    assert family.cache_bytes(d, 0, 2) == 9 * 358_400 == 3_225_600
    assert family.cache_bytes(d, 512, 2) - family.cache_bytes(d, 0, 2) \
        == 512 * 5120 + 8 * 2_621_440
    assert 64 * family.cache_bytes(d, 10240, 2) == 4_904_058_880 == \
        config["arithmetic"]["carry_bytes_64_rows_at_10240"]
    assert config["engine"] == {"max_len": 10240, "slots": 64,
                                "queue_limit": 128}


def test_work_counts_and_the_window_counter():
    from benchmarks.harness.readers import trace_kernel_roofline

    family, d, _ = _family_and_dims()
    s = {"model": d, "dtype_bytes": 2, "decode_steps": 2,
         "decode_attended": [[2000, 1.0]] * 128, "prefill_lengths": [],
         "counters": {family.WINDOW_ENTRIES: {"e": 128 * 512.0}}}
    outside = 3_852_457_984 - 512_163_840 - 5120 - 4 * 2560 * 32 \
        - 9 * (5120 * 4 + 5120 * 3 + 5120 * 16) \
        - 16 * (4 * 64 + 128)
    assert family.matmul_params(d) == outside + 2560 * 200064
    flops, _ = family.phi4f_serve_slice(s)
    e = 6 * 40 * 64
    scan = 9 * (2 * 4 * 5120 + 7 * 5120 * 16)
    token = 2 * family.matmul_params(d) + scan + e * (8 * 2000 + 8 * 512)
    assert flops == pytest.approx(128 * token)
    # one read of the one cache: 64 rows at 2,000 entries of 5,120 bytes
    flops, nbytes = family.phi4f_diff_decode_call(s)
    assert nbytes == 64 * 2000 * 5120 + 64 * 2 * 2560 * 2
    assert flops == e * 64 * 2000
    # one window layer's read: the counter's 512 a row
    flops, nbytes = family.phi4f_window_decode_call(s)
    assert nbytes == 64 * 512 * 5120 + 64 * 2 * 2560 * 2
    record = {"family": family, "slice": s, "device_kind": "TPU v5 lite",
              "trace": {"kernels": {"diff_decode": (1e-3, 2),
                                    "diff_decode_window": (1e-3, 2)}}}
    for work in ("phi4f_diff_decode_call", "phi4f_window_decode_call"):
        assert trace_kernel_roofline.bound(record, work) == "bytes"
    # a program without the window counter: nothing read, no error
    record["slice"] = dict(s, counters={})
    assert trace_kernel_roofline.read(record, ["diff_decode_window"],
                                      "phi4f_window_decode_call") is None
