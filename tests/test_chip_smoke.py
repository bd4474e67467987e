"""chip_smoke.py off the chip: the CPU rehearsal runs every phase at the
tiny size and still FAILS — `"ok": true` is only ever printed for a TPU —
and the full-size run refuses to go past the device phase."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, capture_output=True, text=True, timeout=840,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    return p, lines[:-1], lines[-1]


def _assert_refused(p, last):
    assert p.returncode != 0, p.stderr[-2000:]
    assert last["ok"] is False and last["device"]["platform"] == "cpu", last
    assert '"ok": true' not in p.stdout.splitlines()[-1]


def test_cpu_rehearsal_runs_every_phase_and_still_fails():
    p, phases, last = _smoke("--tiny")
    assert [d["phase"] for d in phases] == [
        "device", "kernels", "train", "serve", "profile"], p.stderr[-4000:]
    failed = [d for d in phases if not d["ok"]]
    assert not failed, (failed, p.stderr[-4000:])
    _assert_refused(p, last)
    by = {d["phase"]: d for d in phases}
    # the rehearsal says what it is: interpreter, no kernel in the program
    assert by["kernels"]["interpret"] is True
    assert by["train"]["round_tpu_custom_calls"] == 0
    assert by["train"]["checkpoint"]["restore_bit_equal"] is True
    assert by["serve"]["bucket_compiles"] == len(by["serve"]["buckets"])


def test_full_size_stops_after_the_device_phase_off_the_chip():
    p, phases, last = _smoke()
    assert [d["phase"] for d in phases] == ["device"]
    assert phases[0]["platform"] == "cpu" and phases[0]["size"] == "full"
    _assert_refused(p, last)
