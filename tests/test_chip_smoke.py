"""chip_smoke.py off the chip: it must refuse, fast, and say what it found.

The script's real run is on the TPU through the chip tool (its output is in
CHANGES.md / PERF.md); what CPU tests can pin is the contract around it: no
accelerator -> non-zero exit naming the platform and no result line; alone
in a directory -> the same; and the pure helper that reads collectives out
of a compiled program's text.
"""

import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120)
    return r, time.monotonic() - t0


def _result_lines(stdout):
    out = []
    for ln in stdout.splitlines():
        try:
            obj = json.loads(ln)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj:
            out.append(obj)
    return out


def test_without_an_accelerator_it_fails_fast_naming_the_platform():
    # run in the real checkout: the program IS there, only the chip is not
    r, dt = _run(REPO, SMOKE)
    assert r.returncode != 0
    assert dt < 60, f"took {dt:.0f}s to notice there is no chip"
    assert "platform 'cpu'" in r.stdout and "not a TPU" in r.stdout
    assert not _result_lines(r.stdout), "printed a result without a chip"


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r, _ = _run(str(tmp_path), "chip_smoke.py")
    assert r.returncode != 0
    assert "FAILED" in r.stdout and "not here" in r.stdout
    assert not _result_lines(r.stdout)


def test_collectives_are_counted_by_participants():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    text = "\n".join([
        "%ag.6 = f32[4,1,2560]{2,1,0:T(1,128)} all-gather(%p), channel_id=1, "
        "replica_groups={{0,1,2,3}}, dimensions={0}",
        "%ps = s32[]{:T(128)} all-reduce(%r), replica_groups={{0,1,3,2}}, "
        "to_apply=%sum",
        "%ags = (f32[1], f32[4]) all-gather-start(%x), "
        "replica_groups=[1,4]<=[4], dimensions={0}",
        "%pair = f32[] all-reduce(%y), replica_groups={{0,1},{2,3}}",
        "%fusion = f32[8] fusion(%z), kind=kLoop",
    ])
    assert chip_smoke._collectives_over(text, 4) == {"all-gather": 2,
                                                     "all-reduce": 1}
    assert chip_smoke._collectives_over(text, 2) == {"all-gather": 0,
                                                     "all-reduce": 1}
