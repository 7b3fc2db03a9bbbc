"""The work counts of the families and ``peaks.py``: the numbers written
out here were worked out by hand from the shapes. A count is looked up in the
family of the cell being run, so the readers get it from the record."""

import json
import os

import pytest

from benchmarks.harness import runtime
from benchmarks.harness.peaks import chip_peaks
from benchmarks.harness.readers import trace_kernel_roofline, trace_step_mfu

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family(data_dir, config):
    cfg = json.load(open(os.path.join(data_dir, "configs", config + ".json")))
    return runtime.load_family(runtime.family_file(data_dir, cfg)), cfg


work, _ = _family(BENCH, "bert-base")

BERT = {"vocab_size": 30522, "hidden": 768, "n_layers": 12, "n_heads": 12,
        "ffn_size": 3072, "max_len": 512}
GPT2 = {"vocab_size": 50257, "hidden": 768, "n_layers": 12, "n_heads": 12,
        "ffn_size": 3072, "max_len": 1024}


class _Zoo:  # the attributes bert_train_flops_per_token reads
    hidden, n_layers, ffn_size, vocab_size = 768, 12, 3072, 30522


@pytest.mark.parametrize("seq", [128, 512])
def test_encoder_flops_equal_the_programs_own(seq):
    from deeplearning4j_tpu.bench.flops import bert_train_flops_per_token

    assert work.encoder_train_flops_per_token(BERT, seq) == \
        bert_train_flops_per_token(_Zoo, seq)


def test_encoder_flops_written_out():
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 30522 = 108,375,552
    assert work.matmul_params(BERT) == 108_375_552
    # 6 x 108,375,552 + 12 x 12 x 768 x 512 = 706,876,416 a token
    assert work.encoder_train_flops_per_token(BERT, 512) == 706_876_416
    s = {"steps": 2, "batch": 32, "seq": 512, "model": BERT}
    assert work.encoder_train_step_slice(s) == (2 * 16384 * 706_876_416, None)


def test_decoder_matmul_parameters():
    assert 12 * 7_077_888 + 768 * 50_257 == 123_532_032
    assert work.matmul_params(GPT2) == 123_532_032
    # 2 N + 4 x 12 x 768 x 250 entries attended
    assert work.decoder_flops_per_token(GPT2, 250) == \
        2 * 123_532_032 + 9_216_000


def test_decoder_slice_counts_prefill_and_decode():
    # the second token has half of its time in the slice, the prefill all
    s = {"model": GPT2, "decode_attended": [[100, 1.0], [300, 0.5]],
         "prefill_lengths": [[64, 1.0]]}
    want = (1.5 * (2 * 123_532_032) + 4 * 12 * 768 * 250
            + 64 * (2 * 123_532_032 + 4 * 12 * 768 * 32.5))
    assert work.decoder_serve_slice(s)[0] == pytest.approx(want, rel=1e-12)


def test_flash_kernels_at_the_train_cells_shapes():
    s = {"batch": 32, "seq": 512, "model": BERT, "dtype_bytes": 2}
    # 32 x 12 heads x 512^2 x 64: QK^T and PV, 2 FLOPs a multiply-add
    assert work.flash_fwd_call(s) == (4 * 32 * 12 * 512 * 512 * 64,
                                      4 * 32 * 12 * 512 * 64 * 2
                                      + 4 * 32 * 12 * 512)
    assert work.flash_fwd_call(s) == (25_769_803_776, 101_449_728)
    assert work.flash_bwd_call(s) == (51_539_607_552, 202_113_024)


def test_flash_decode_at_the_serve_cells_shapes():
    # two steps, three rows decoded in all, 600 cache entries attended
    s = {"model": GPT2, "dtype_bytes": 2, "decode_steps": 2,
         "decode_attended": [[100, 1.0], [200, 1.0], [300, 1.0]]}
    flops, nbytes = work.flash_decode_call(s)
    assert flops == 4 * 300 * 12 * 64            # 300 entries a call
    assert nbytes == 2 * 300 * 768 * 2 + 2 * 1.5 * 768 * 2


def test_peaks_and_unknown_kind():
    pk = chip_peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v9 imaginary")


def _record(kernels, slice_, window_s=1.0, family=work):
    return {"trace": {"kernels": kernels, "window_s": window_s,
                      "busy_s": window_s}, "slice": slice_, "family": family,
            "device_kind": "TPU v5 lite", "chips": 1}


def test_kernel_roofline_reader():
    s = {"batch": 32, "seq": 512, "model": BERT, "dtype_bytes": 2}
    rec = _record({"flash_fwd": [12 * 1e-3, 12]}, s)
    got = trace_kernel_roofline.read(rec, ["flash_fwd"], "flash_fwd_call")
    # 25.77 GFLOP at 197 TFLOP/s is 0.1308 ms of a 1 ms call
    assert got == pytest.approx(100 * 25_769_803_776 / 197e12 / 1e-3)
    assert trace_kernel_roofline.bound(rec, "flash_fwd_call") == "flops"
    # both backward kernels together; a trace without one reads nothing
    rec = _record({"flash_bwd_dq": [0.012, 12], "flash_bwd_dkv": [0.024, 12]}, s)
    got = trace_kernel_roofline.read(
        rec, ["flash_bwd_dq", "flash_bwd_dkv"], "flash_bwd_call")
    assert got == pytest.approx(100 * 51_539_607_552 / 197e12 / 3e-3)
    assert trace_kernel_roofline.read(
        _record({"flash_bwd_dq": [0.012, 12]}, s),
        ["flash_bwd_dq", "flash_bwd_dkv"], "flash_bwd_call") is None
    d = {"model": GPT2, "dtype_bytes": 2, "decode_steps": 1,
         "decode_attended": [[250, 1.0]] * 128}
    rec = _record({"flash_decode": [12 * 2e-3, 12]}, d)
    assert trace_kernel_roofline.bound(rec, "flash_decode_call") == "bytes"
    nbytes = 2 * 250 * 128 * 768 * 2 + 2 * 128 * 768 * 2
    assert trace_kernel_roofline.read(rec, ["flash_decode"],
                                      "flash_decode_call") == \
        pytest.approx(100 * nbytes / 819e9 / 2e-3)


@pytest.mark.parametrize("metric, slice_", [
    ("flash_fwd_roofline", {"batch": 32, "seq": 512, "model": BERT, "dtype_bytes": 2}),
    ("flash_bwd_roofline", {"batch": 32, "seq": 512, "model": BERT, "dtype_bytes": 2}),
    ("flash_decode_roofline", {"model": GPT2, "dtype_bytes": 2, "decode_steps": 1,
                               "decode_attended": [[250, 1.0]] * 128}),
])
def test_each_roofline_metrics_file_says_which_bound(metric, slice_):
    f = json.load(open(os.path.join(BENCH, "layer_metrics",
                                    metric + ".json")))
    assert f["bound"] == trace_kernel_roofline.bound(
        _record({}, slice_), f["params"]["work"])


def test_step_mfu_reader():
    s = {"steps": 20, "batch": 32, "seq": 512, "model": BERT}
    rec = _record({}, s, window_s=2.5)
    assert trace_step_mfu.read(rec, "encoder_train_step_slice") == \
        pytest.approx(100 * 20 * 16384 * 706_876_416 / (2.5 * 197e12))
    # a serve slice: the tokens' FLOPs over the trace's device window
    d = {"model": GPT2, "decode_attended": [[250, 1.0]] * 128,
         "prefill_lengths": []}
    assert trace_step_mfu.read(_record({}, d, window_s=0.125),
                               "decoder_serve_slice") == pytest.approx(
        100 * 128 * (2 * 123_532_032 + 9_216_000) / (0.125 * 197e12))
    rec["device_kind"] = "TPU v9 imaginary"
    with pytest.raises(ValueError):
        trace_step_mfu.read(rec, "encoder_train_step_slice")
    assert trace_step_mfu.read({"trace": None, "slice": s},
                               "encoder_train_step_slice") is None


def test_the_configurations_sizes_come_from_their_family():
    for name, want in (("bert-base", BERT), ("gpt2-small", GPT2)):
        family, cfg = _family(BENCH, name)
        assert family.dims(cfg) == want
    # a request at position 220 holds 220 keys and values a layer
    assert work.cache_bytes(GPT2, 220, 2) == 220 * 2 * 12 * 768 * 2


def test_a_second_familys_counts_are_found_in_its_own_file():
    """The fixture's LSTM: three layers of 24, vocabulary 40. A token costs
    3 ``h RW`` + 2 ``x W`` of 24 x 96 and the 24 x 40 head, whatever its
    position; its state is ``h`` and ``c`` a layer."""
    lstm, cfg = _family(os.path.join(BENCH, "tests", "data"), "tiny-lstm")
    d = lstm.dims(cfg)
    assert d == {"vocab_size": 40, "hidden": 24, "layers": 3}
    assert lstm.flops_per_token(d) == 2 * (5 * 24 * 96 + 24 * 40) == 24_960
    assert lstm.cache_bytes(d, 7, 4) == lstm.cache_bytes(d, 700, 4) == 576
    s = {"model": d, "decode_attended": [[9, 1.0], [30, 0.5]],
         "prefill_lengths": [[8, 0.25]], "counters": {
             "dl4j_tpu_generate_tokens_total": {"decode": 3.0}}}
    rec = _record({}, s, window_s=0.5, family=lstm)
    assert trace_step_mfu.read(rec, "lstm_serve_slice") == pytest.approx(
        100 * 3.5 * 24_960 / (0.5 * 197e12))
    # work that only the run knows: the program's own counter over the slice
    assert trace_step_mfu.read(rec, "lstm_emitted_slice") == pytest.approx(
        100 * 3 * 24_960 / (0.5 * 197e12))
    # the transformer's counts are not this family's
    with pytest.raises(AttributeError):
        trace_step_mfu.read(rec, "decoder_serve_slice")


def test_a_traced_slice_carries_the_programs_counters_to_the_family(
        monkeypatch):
    """A whole traced rehearsal of the fixture's cell, with a device trace
    stood in (the CPU has none): the slice the serve driver records holds
    what the program's counter rose by (``slice_counters`` in the
    configuration), and both readings of the step's share reach the
    family's own functions. The shares are of a made-up window and are
    compared with each other only."""
    from benchmarks import run as bench_run
    from benchmarks.harness import serve_driver

    data = os.path.join(BENCH, "tests", "data")
    fake = {"window_s": 0.25, "busy_s": 0.2, "chips_traced": 1, "ops": {},
            "kernels": {}, "top_ops": [["fusion.1", 0.1]], "idle_gaps": []}
    report = serve_driver.device_report
    monkeypatch.setattr(runtime.TraceSlice, "summary", lambda self: fake)
    monkeypatch.setattr(serve_driver, "device_report", lambda *a: dict(
        report(*a), kind="TPU v5 lite"))
    line = bench_run.main([
        "--workload", "tiny-lstm-closed", "--seed", "3000000033",
        "--seconds", "2", "--trace", "1", "--rehearse", "--data-dir", data,
        "--manifest", os.path.join(data, "manifest.json")])
    assert line["correct"] is True
    m = line["metrics"]
    assert set(m) == {"lstm_step_mfu", "lstm_emitted_mfu",
                      "lstm_decode_step_ms"}
    # by shares with the prefills, or whole from the program's counter: the
    # same tokens to within the slice's edges and the prompts prefilled
    assert 0 < m["lstm_emitted_mfu"]["value"] < 3 * m["lstm_step_mfu"]["value"]
    assert m["lstm_step_mfu"]["value"] < 3 * m["lstm_emitted_mfu"]["value"]
    assert line["device"]["busy_s"] == 0.2
