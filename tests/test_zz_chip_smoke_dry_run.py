"""``chip_smoke.py --dry-run-cpu`` end to end: every phase passes at toy
sizes, the result says it is a dry run, and with
``JAX_COMPILATION_CACHE_DIR`` set the compile-cache helper leaves the
directory to it. Four virtual devices, so the four-chip phase — which the
driver's one-chip run skips — is rehearsed too.

The file sorts last on purpose: this is tier-1's dearest single case
(~20 s, one process), and the tier-1 command runs under a wall-clock
limit, so it goes after the cheap ones.
"""

import json

from test_chip_smoke import SMOKE, finish, start


def test_dry_mode_passes_and_labels_itself(tmp_path):
    rc, stdout, stderr = finish(
        start([SMOKE, "--dry-run-cpu"], cache_dir=tmp_path, devices=4))
    assert rc == 0, stderr[-2000:]
    report_line, verdict_line = stdout.strip().splitlines()[-2:]
    # the last line holds exactly the keys the driver's chip check reads
    device = {"platform": "cpu", "kind": "cpu", "count": 4}
    assert json.loads(verdict_line) == {"ok": True, "device": device}
    result = json.loads(report_line)
    assert result["dry_run"] is True and result["device"] == device
    assert result["compile_cache_dir"] == str(tmp_path)
    for phase in ("train", "serve", "kernels", "four_chips"):
        assert result["phases"][phase]["ok"] is True
        assert result["phases"][phase]["wall_s"] > 0
    train = result["phases"]["train"]
    assert train["last_loss"] < train["first_loss"]
    for mesh in ("dp4_zero1", "dp2_tp2"):
        four = result["phases"]["four_chips"][mesh]
        assert four["partitioned_leaves"] > 0
        assert abs(four["first_loss"] - train["first_loss"]) < 1e-3
