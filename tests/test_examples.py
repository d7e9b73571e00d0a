"""Every example in examples/ must run end-to-end in --smoke mode.

Examples are user-facing documentation; a broken example is a broken
contract. Examples sharing a mesh size run sequentially in ONE
subprocess (forced-CPU mesh, same environment as the rest of the suite):
the interpreter + jax import tax is paid once per mesh size instead of
once per script, which keeps this job inside the tier-1 budget. The
driver prints an ``OK <script>`` marker per example so a group failure
still attributes to the script that broke."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(_REPO, "examples"))
    if f.endswith(".py"))


# XLA's in-process CPU collectives abort if any participant thread is
# starved >40 s (rendezvous.cc hard deadline, no flag). This harness has
# ONE core: an 8-thread per-step-psum rendezvous under cgroup scheduling
# jitter trips it (seen deterministically mid-suite for the dp example).
# The parallel math is identical at any mesh size, so the heavy-collective
# examples run their smoke tests on reduced meshes (2 for the per-step-psum
# dp example, 4 for the multi-mode parallel transformer — the smallest
# count that still exercises its composed 2-D branch); everything else
# keeps the suite-standard 8. Device count is fixed per process, so the
# groups below are exactly the mesh sizes.
_DEVICE_COUNT = {"data_parallel_training.py": 2,
                 "parallel_transformer.py": 4}

_GROUPS: dict = {}
for _f in _EXAMPLES:
    _GROUPS.setdefault(_DEVICE_COUNT.get(_f, 8), []).append(_f)

_DRIVER = r"""
import runpy, sys, traceback
for s in sys.argv[1:]:
    sys.argv = [s, "--smoke"]
    try:
        runpy.run_path(s, run_name="__main__")
    except SystemExit as e:
        if e.code not in (None, 0):
            print(f"FAILED {s} (SystemExit {e.code})", flush=True)
            sys.exit(1)
    except BaseException:
        print(f"FAILED {s}:", flush=True)
        traceback.print_exc()
        sys.exit(1)
    print(f"OK {s}", flush=True)
"""


@pytest.mark.parametrize("n_dev", sorted(_GROUPS),
                         ids=lambda n: f"mesh{n}")
def test_example_smoke(n_dev):
    scripts = _GROUPS[n_dev]
    # the children inherit the suite's persistent compile cache
    # (conftest.py): the smoke groups are compile-dominated, the mesh8
    # group most of all
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=_REPO)
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER,
         *(os.path.join(_REPO, "examples", s) for s in scripts)],
        capture_output=True, text=True, env=env, timeout=900, cwd=_REPO)
    assert proc.returncode == 0, (
        f"mesh{n_dev} group ({', '.join(scripts)}) failed "
        f"(rc={proc.returncode}).\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    # every script in the group must have reported, in order
    for s in scripts:
        assert f"OK {os.path.join(_REPO, 'examples', s)}" in proc.stdout, (
            f"{s} did not report OK\nstdout:\n{proc.stdout}")
