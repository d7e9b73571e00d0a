"""Backend parity: the same network must produce the same numbers on the
compiled TPU backend as on CPU.

Parity: the reference cross-validates its accelerated helper path against
the plain CPU path (``deeplearning4j-cuda/src/test/.../CuDNNGradientChecks
.java``, ``TestConvolution.java`` — helper on vs off, assert agreement).
Here the two "backends" are the default JAX platform (the real TPU chip
when this harness has one) and the forced-CPU platform the rest of the
suite runs on.

Mechanics: the whole suite pins ``JAX_PLATFORMS=cpu`` before JAX init
(``conftest.py``), so the parent never holds the chip and the TPU half
runs in a SUBPROCESS with a clean environment, one child at a time (a
chip belongs to one process). Skips loudly when no accelerator is
present. Matmul/conv
precision is pinned to ``highest`` on both sides so the comparison checks
the compilation path, not bf16 MXU rounding.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
sys.path.insert(0, %(repo)r)
plat = jax.devices()[0].platform
if plat == "cpu":
    print(json.dumps({"platform": "cpu"}))
    sys.exit(0)
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

conf = MultiLayerConfiguration.from_json(open(sys.argv[1]).read())
net = MultiLayerNetwork(conf).init()
d = np.load(sys.argv[2])
x, y = d["x"], d["y"]
out = np.asarray(net.output(x), dtype=np.float64)
score = float(net.score_for(x, y))
net.fit_batch(x, y)
score_after = float(net.score_for(x, y))
np.savez(sys.argv[3], out=out)
print(json.dumps({"platform": plat, "score": score,
                  "score_after": score_after}))
"""



_CHILD_ENV_DROP = ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_ENABLE_X64",
                   "DL4JTPU_FLASH_ATTENTION", "DL4JTPU_FLASH_BWD")

_ACCEL_PROBE = None


def _accel_plausible() -> bool:
    """Zero-cost pre-gate: is there any accelerator DEVICE NODE on this
    machine at all? A box with no /dev/accel*, /dev/vfio or /dev/nvidia*
    and no TPU env cannot have a reachable chip, so the 90 s init probe
    below is pure waiting — the PR-8 tier-1 note measured that wait as
    ~10% of the verify budget on the chipless reference box."""
    import glob
    if os.environ.get("TPU_NAME") or os.environ.get("TPU_WORKER_ID"):
        return True
    # /dev/kfd is the ROCm compute node; plain DRM render nodes
    # (/dev/dri/renderD*) are NOT included — any iGPU would resurrect
    # the 90 s probe on CPU-only boxes
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")
                or glob.glob("/dev/nvidia*") or glob.glob("/dev/kfd"))


def _accel_reachable() -> bool:
    """ONE cheap per-session probe: can a clean child initialize a
    non-CPU JAX platform at all? Where a device node exists but the chip
    cannot be opened (another process holds it), jax init fails or hangs
    in the child — without this gate every parity child would burn its
    full per-test timeout (2×420 s of an 870 s run). The probe bounds
    that to one 90 s wait (skipped outright when no device node exists),
    after which every parity test skips loudly."""
    global _ACCEL_PROBE
    if _ACCEL_PROBE is None:
        if not _accel_plausible():
            _ACCEL_PROBE = False
            return _ACCEL_PROBE
        env = {k: v for k, v in os.environ.items()
               if k not in _CHILD_ENV_DROP}
        try:
            proc = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(jax.devices()[0].platform)"],
                capture_output=True, text=True, env=env, timeout=90)
            _ACCEL_PROBE = (proc.returncode == 0 and proc.stdout.strip()
                            .splitlines()[-1] != "cpu")
        except subprocess.TimeoutExpired:
            _ACCEL_PROBE = False
    return _ACCEL_PROBE


def _run_accel_child(child_src, *argv, timeout=420):
    """Run an accelerator-side child with the suite's CPU pins (and the
    framework's kernel-routing toggles) stripped; returns the child's
    last-stdout-line JSON. ONE copy of the scaffolding for every
    backend-parity test so child environments cannot drift."""
    if not _accel_reachable():
        pytest.skip("no reachable accelerator platform — backend-parity "
                    "tests need the TPU harness")
    env = {k: v for k, v in os.environ.items() if k not in _CHILD_ENV_DROP}
    proc = subprocess.run(
        [sys.executable, "-c", child_src % {"repo": _REPO}, *map(str, argv)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert proc.returncode == 0, f"accelerator child failed:\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _conf():
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (BatchNormalization,
                                                   ConvolutionLayer,
                                                   DenseLayer, OutputLayer,
                                                   SubsamplingLayer)
    return (NeuralNetConfiguration.builder().seed(77).updater("sgd")
            .learning_rate(0.05).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    activation="relu"))
            .layer(BatchNormalization())
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(10, 10, 1)).build())


class TestBackendParity:
    def test_tpu_matches_cpu(self, rng, tmp_path):
        import jax

        conf = _conf()
        conf_path = tmp_path / "conf.json"
        conf_path.write_text(conf.to_json())
        x = rng.normal(size=(8, 10, 10, 1)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
        data_path = tmp_path / "data.npz"
        np.savez(data_path, x=x, y=y)
        out_path = tmp_path / "tpu_out.npz"

        info = _run_accel_child(_CHILD, conf_path, data_path, out_path)
        if info["platform"] == "cpu":
            pytest.skip("no accelerator platform available — backend-parity "
                        "test needs the TPU harness")

        # CPU side, identical init (deterministic from config seed), f32
        with jax.default_matmul_precision("highest"):
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
            net = MultiLayerNetwork(_conf()).init()
            cpu_out = np.asarray(net.output(x), dtype=np.float64)
            cpu_score = float(net.score_for(x, y))
            net.fit_batch(x, y)
            cpu_score_after = float(net.score_for(x, y))

        tpu_out = np.load(out_path)["out"]
        np.testing.assert_allclose(tpu_out, cpu_out, rtol=1e-4, atol=1e-5)
        assert info["score"] == pytest.approx(cpu_score, rel=1e-4)
        # one SGD step: compiled update path agrees across backends
        assert info["score_after"] == pytest.approx(cpu_score_after, rel=1e-3)


_FLASH_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
sys.path.insert(0, %(repo)r)
plat = jax.devices()[0].platform
if plat == "cpu":
    print(json.dumps({"platform": "cpu"}))
    sys.exit(0)
import os
from deeplearning4j_tpu.ops.attention import dot_product_attention
from deeplearning4j_tpu.ops.flash_attention import flash_attention

d = np.load(sys.argv[1])
q, k, v = (jnp.asarray(d[n]) for n in ("q", "k", "v"))

def gradsum(attn):
    def f(q, k, v):
        return jnp.sum(jnp.tanh(attn(q, k, v)))  # bounded loss, f32
    return jax.grad(f, argnums=(0, 1, 2))

os.environ["DL4JTPU_FLASH_ATTENTION"] = "0"
g_xla = jax.jit(gradsum(lambda q, k, v: dot_product_attention(
    q, k, v, causal=True)))(q, k, v)
del os.environ["DL4JTPU_FLASH_ATTENTION"]
g_flash = jax.jit(gradsum(lambda q, k, v: flash_attention(
    q, k, v, True)))(q, k, v)
diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(g_xla, g_flash)]
scale = [float(jnp.max(jnp.abs(a))) for a in g_xla]
print(json.dumps({"platform": plat, "diffs": diffs, "scale": scale}))
"""


class TestFlashBackwardOnChip:
    def test_pallas_backward_matches_xla_on_chip(self, rng, tmp_path):
        """The Pallas backward (one call for dq, dk and dv) vs XLA autodiff
        ON THE REAL CHIP at a size that engages the 1024x1024 tile
        dispatch and two key blocks, so the dq scratch is summed into
        across them (the CPU interpret tests can't see Mosaic lowering
        bugs). f32, causal."""
        q = rng.normal(size=(1, 2048, 2, 64)).astype(np.float32)
        k = rng.normal(size=(1, 2048, 2, 64)).astype(np.float32)
        v = rng.normal(size=(1, 2048, 2, 64)).astype(np.float32)
        data_path = tmp_path / "qkv.npz"
        np.savez(data_path, q=q, k=k, v=v)
        info = _run_accel_child(_FLASH_CHILD, data_path)
        if info["platform"] == "cpu":
            pytest.skip("no accelerator platform available")
        for name, diff, scale in zip("qkv", info["diffs"], info["scale"]):
            assert diff <= 2e-3 * max(scale, 1.0), (
                f"d{name} on-chip max diff {diff} vs grad scale {scale}")
