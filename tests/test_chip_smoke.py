"""chip_smoke.py on the CPU: the toy-size switch drives every leg to the
end, and without the switch a machine with no TPU is refused.

The script owns its process (and, on the chip, the chip), so each case runs
it as a child the way the driver does. The children are CPU-pinned."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("DL4JTPU_FLASH_ATTENTION", None)
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=env, timeout=600, cwd=_REPO)


def _summary(proc):
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout:\n{proc.stdout[-4000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}")
    lines = proc.stdout.strip().splitlines()
    # the result line is the last one and has these keys and no others
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    assert lines[-2].startswith("summary: ")
    out = json.loads(lines[-2][len("summary: "):])
    assert out["small"] is True and out["claim"] is None
    assert out["failed"] == []
    assert not any("error" in leg for leg in out["legs"].values())
    out["device"] = result["device"]
    return out


def test_small_switch_runs_the_single_device_legs():
    out = _summary(_run("--small"))
    assert out["device"]["count"] == 1
    assert list(out["legs"]) == ["kernel", "train", "serve"]
    # the toy train step still went through the kernel (interpreted
    # because --small asked), and the server compiled nothing mid-request
    assert out["legs"]["train"]["kernel_calls_in_step"] > 0
    assert out["legs"]["serve"]["programs_compiled"] > 0


def test_refuses_to_run_without_a_tpu():
    proc = _run()
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "tpu" in proc.stderr
    # the device line is all it printed: no result of any kind
    assert "{" not in proc.stdout


@pytest.mark.slow   # ~30 s on top of a tier-1 run that is already over budget
def test_small_switch_runs_the_four_device_legs():
    out = _summary(_run("--small", devices=4))
    assert list(out["legs"]) == ["kernel", "train", "serve", "dp4", "sp4"]
    assert out["legs"]["sp4"]["kernel_calls_in_step"] > 0
