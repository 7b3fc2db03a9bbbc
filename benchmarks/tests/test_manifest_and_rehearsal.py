"""``BENCHMARK.json`` against the files it names, and the command itself
rehearsed on the CPU (tiny widths; ``device.platform == "cpu"``; these
numbers are never written anywhere)."""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
DATA = os.path.join(BENCH, "tests", "data")
TEST_DATA = ["--data-dir", DATA, "--manifest",
             os.path.join(DATA, "manifest.json")]


def _names(kind):
    return [e["name"] for e in MANIFEST[kind]]


def _files(sub):
    return sorted(os.path.splitext(os.path.basename(p))[0]
                  for p in glob.glob(os.path.join(BENCH, sub, "*.json")))


def _load(sub, name):
    return json.load(open(os.path.join(BENCH, sub, name + ".json")))


def test_names_and_units_use_the_allowed_characters():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MANIFEST[kind]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in MANIFEST["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(set(_names(kind))) == len(_names(kind))
    assert len(set(_names("end_to_end") + _names("per_layer"))) == \
        len(_names("end_to_end")) + len(_names("per_layer"))


def test_every_entry_has_its_file_and_the_reverse():
    assert _files("workloads") == sorted(_names("workloads"))
    assert _files("configs") == sorted(_names("configs"))
    assert _files("layer_metrics") == sorted(_names("per_layer"))
    assert _files("traffic") == sorted({w["traffic"]
                                        for w in MANIFEST["workloads"]})
    for c in MANIFEST["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert _load("configs", c["name"])["reduced"] == c["reduced"]
    for w in MANIFEST["workloads"]:
        cell = _load("workloads", w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
    readers = {os.path.splitext(os.path.basename(p))[0] for p in glob.glob(
        os.path.join(BENCH, "harness", "readers", "*.py"))}
    for m in MANIFEST["per_layer"]:
        f = _load("layer_metrics", m["name"])
        for key in ("name", "layer", "unit", "source", "moves", "workloads"):
            assert f[key] == m[key], (m["name"], key)
        assert f["reader"] in readers


@pytest.mark.parametrize("data", [BENCH, DATA], ids=["benchmark", "tests"])
def test_every_configuration_names_a_family_that_is_there_and_the_reverse(data):
    named = {json.load(open(p)).get("family") for p in glob.glob(
        os.path.join(data, "configs", "*.json"))}
    files = {os.path.splitext(os.path.basename(p))[0] for p in glob.glob(
        os.path.join(data, "families", "*.py"))}
    assert None not in named, "a configuration without a family"
    assert named == files
    if data == BENCH:  # the manifest's configurations are those files
        assert all("family" in _load("configs", c["name"])
                   for c in MANIFEST["configs"])


def test_no_file_of_the_harness_knows_a_familys_keys_or_names():
    """The acceptance criterion's grep, with the fixture's family beside it:
    outside docstrings nothing under ``harness/`` or in ``run.py`` names a
    family, its keys or its sizes."""
    words = re.compile(r"BLOCK_KEYS|ffn_size|n_heads|pos_emb|\bwq\b|n_layers"
                       r"|lstm|LSTM|preln|first_rw|encoder_loss")
    paths = glob.glob(os.path.join(BENCH, "harness", "**", "*.py"),
                      recursive=True) + [os.path.join(BENCH, "run.py"),
                                         os.path.join(BENCH, "limits.py")]
    assert len(paths) > 15
    for path in paths:
        code = re.sub(r'"""[\s\S]*?"""', "", open(path).read())
        code = re.sub(r"#.*", "", code)
        assert not words.search(code), (path, words.search(code).group(0))


def test_each_layer_metric_moves_a_metric_its_cells_report():
    cells = set(_names("workloads"))
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in MANIFEST["end_to_end"]}
    assert reports["setup_s"] == cells
    for m in MANIFEST["per_layer"]:
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
    for cell in cells:  # setup_s, one more end-to-end metric, one per-layer
        assert sum(cell in r for r in reports.values()) >= 2
        assert any(cell in m["workloads"] for m in MANIFEST["per_layer"])


def _run(args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py")] + args,
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", _names("workloads"))
def test_rehearsal_ends_in_the_contracts_line(cell, trace):
    p = _run(["--workload", cell, "--seed", "3000000019", "--seconds", "2",
              "--trace", str(trace), "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) - {"breakdown"} == RESULT_KEYS
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    if trace == 0:
        want = {e["name"] for e in MANIFEST["end_to_end"]
                if cell in e.get("workloads", [cell])}
        assert set(line["metrics"]) == want
        for m in line["metrics"].values():
            assert m["value"] > 0 and UNIT.match(m["unit"])
    else:  # nothing of a device can be read on the CPU, so no share is made up
        assert set(line["metrics"]) <= set(_names("per_layer"))
    # every number compared is printed beside its limit, last on stderr
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "limit" in t for t in tail)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_second_family_is_rehearsed_from_the_test_data_alone(trace):
    """The zoo's TextGenerationLSTM behind the real DecodeEngine: recurrent
    state, two stacked groups, its own reference and counts, all in files
    under ``tests/data`` (no file of ``harness/`` or ``run.py`` knows it:
    the test above)."""
    p = _run(["--workload", "tiny-lstm-closed", "--seed", "3000000029",
              "--seconds", "2", "--trace", str(trace), "--rehearse"]
             + TEST_DATA)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) - {"breakdown"} == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    assert set(line["checks"]) == {"served_logit_gap",
                                   "served_mean_logit_gap"}
    if trace == 0:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                        "setup_s"}
    else:  # the CPU has no device trace: the program's histogram is read
        assert set(line["metrics"]) == {"lstm_decode_step_ms"}
    # 3 layers x (h, c) x 24 x 4 bytes a request, whatever its position
    held = float(re.search(r"kv_filled_bytes[^:]*: ([0-9.e+-]+)",
                           p.stderr).group(1))
    assert 0 < held <= 4 * 576


@pytest.mark.parametrize("family", [None, "no-such-family"])
def test_a_configuration_without_its_family_prints_no_result(tmp_path, family):
    data = shutil.copytree(DATA, tmp_path / "data")
    path = data / "configs" / "tiny-gpt.json"
    config = json.load(open(path))
    if family is None:
        del config["family"]
    else:
        config["family"] = family
    json.dump(config, open(path, "w"))
    p = _run(["--workload", "tiny-gpt-open", "--seed", "5", "--seconds", "1",
              "--rehearse", "--data-dir", str(data), "--manifest",
              str(data / "manifest.json")])
    assert p.returncode != 0 and not p.stdout.strip()
    assert "family" in p.stderr.splitlines()[-1]


def _losses(stderr):
    m = re.search(r"program_losses: (\[.*\])", stderr)
    return json.loads(m.group(1))


def test_mesh_of_four_goes_through_the_distributed_trainer_from_data_alone():
    args = ["--seed", "3000000007", "--seconds", "1", "--rehearse"] + TEST_DATA
    one = _run(["--workload", "tiny-bert"] + args)
    four = _run(["--workload", "tiny-bert-dp4"] + args, env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert one.returncode == 0 and four.returncode == 0, four.stderr[-2000:]
    l1, l4 = _losses(one.stderr), _losses(four.stderr)
    assert l4[0] == pytest.approx(l1[0], rel=1e-6)
    line = json.loads(four.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4 and line["correct"] is True
    # without the four devices the same cell refuses to run
    none = _run(["--workload", "tiny-bert-dp4"] + args)
    assert none.returncode != 0 and not none.stdout.strip()


def test_open_loop_traffic_is_data_too():
    p = _run(["--workload", "tiny-gpt-open", "--seed", "5", "--seconds", "2",
              "--rehearse"] + TEST_DATA)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 10
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                    "setup_s"}


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run(["--workload", _names("workloads")[0], "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert not p.stdout.strip()
