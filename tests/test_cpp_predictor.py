"""C++ serving predictor (csrc/predictor, PJRT C API).

The artifact contract (``.mlir`` + ``.copts.pb`` + ``.pdweights`` +
``.pdmodel.json``) is validated on CPU; the device e2e runs load the
``libtpu.so`` of the installed ``libtpu`` package as the PJRT plugin and
skip where no chip answers it (this sandbox; they run on a chip machine).
"""
import json
import os
import struct
import subprocess

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRED_DIR = os.path.join(REPO, "csrc", "predictor")
CLI = os.path.join(PRED_DIR, "predictor_cli")


def _build():
    r = subprocess.run(["make", "-C", PRED_DIR], capture_output=True,
                       text=True, timeout=300)
    if r.returncode != 0:
        pytest.skip(f"predictor build failed: {r.stderr[-500:]}")


def _run_cli_on_chip(prefix):
    """Run predictor_cli over `<prefix>.in0.bin` through libtpu's PJRT
    client; skip when this machine has no TPU chip for it to open."""
    try:
        import libtpu
    except ImportError:
        pytest.skip("libtpu is not installed")
    plugin = os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
    env = dict(os.environ)
    env.update({"TPU_SKIP_MDS_QUERY": "1",
                "TPU_WORKER_HOSTNAMES": "localhost"})
    try:
        r = subprocess.run(
            [CLI, prefix, plugin, prefix + ".in0.bin"],
            env=env, capture_output=True, text=True, timeout=180)
    except subprocess.TimeoutExpired:
        pytest.skip("no TPU chip answered libtpu within 180 s")
    if r.returncode != 0:
        pytest.skip(f"no TPU chip for libtpu's PJRT client: "
                    f"{r.stderr[-400:]}")
    return r


def _export_tiny(tmp_path):
    import paddle_tpu as paddle
    from paddle_tpu import inference, nn
    paddle.seed(0)
    model = nn.Linear(4, 3)
    x = np.arange(8, dtype=np.float32).reshape(2, 4)
    prefix = str(tmp_path / "tiny")
    inference.export_model(model, [x], prefix)
    expected = model(paddle.to_tensor(x)).numpy()
    return prefix, x, expected


def test_export_writes_cpp_artifacts(tmp_path):
    prefix, x, _ = _export_tiny(tmp_path)
    # stablehlo portable bytecode magic
    head = open(prefix + ".mlir", "rb").read(4)
    assert head == b"ML\xefR"
    assert os.path.getsize(prefix + ".copts.pb") > 0
    meta = json.load(open(prefix + ".pdmodel.json"))
    assert meta["inputs"][0]["pjrt_type"] == 11  # F32
    # weights binary: magic + count, parseable end to end
    raw = open(prefix + ".pdweights", "rb").read()
    assert raw[:4] == b"PDW1"
    (count,) = struct.unpack_from("<I", raw, 4)
    assert count == meta["n_weights"] == 2  # weight + bias
    off = 8
    parsed = []
    for _ in range(count):
        code, ndim = struct.unpack_from("<II", raw, off)
        off += 8
        dims = struct.unpack_from(f"<{ndim}q", raw, off)
        off += 8 * ndim
        (nbytes,) = struct.unpack_from("<Q", raw, off)
        off += 8
        arr = np.frombuffer(raw, np.float32, nbytes // 4, off)
        off += nbytes
        parsed.append((code, dims, arr))
    assert off == len(raw)
    shapes = sorted(tuple(d) for _, d, _ in parsed)
    assert shapes == [(3,), (4, 3)]


def test_cpp_predictor_runs_exported_model_on_device(tmp_path):
    """The AnalysisPredictor-parity e2e: C++ binary loads the artifact,
    compiles via the PJRT plugin, and matches the Python forward."""
    _build()
    prefix, x, expected = _export_tiny(tmp_path)
    x.tofile(prefix + ".in0.bin")
    r = _run_cli_on_chip(prefix)
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["num_outputs"] == 1
    np.testing.assert_allclose(result["outputs"][0]["f32_sum"],
                               float(expected.sum()), rtol=1e-4)
    out = np.fromfile(prefix + ".out0.bin", np.float32).reshape(
        expected.shape)
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)


def _export_quantized_tiny(tmp_path):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.quantization import PostTrainingQuantization
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    rng = np.random.RandomState(0)
    x = rng.rand(2, 4).astype(np.float32)
    ptq = PostTrainingQuantization(model, algo="abs_max")
    ptq.quantize([rng.rand(2, 4).astype(np.float32) for _ in range(3)])
    prefix = str(tmp_path / "tiny_int8")
    ptq.save_quantized_model(prefix, input_spec=[x])
    expected = model(paddle.to_tensor(x)).numpy()  # folded == dequant path
    return prefix, x, expected


def test_quantized_artifact_carries_int8(tmp_path):
    from _artifact_utils import parse_pdweights_types
    prefix, x, _ = _export_quantized_tiny(tmp_path)
    codes = parse_pdweights_types(prefix + ".pdweights")
    assert codes.count(2) == 2  # two int8 Linear weights (PJRT S8)
    meta = json.load(open(prefix + ".pdmodel.json"))
    assert len(meta["quantized"]) == 2


def test_cpp_predictor_serves_int8_model_on_device(tmp_path):
    """The C++ predictor CLI serves the int8-weight artifact within
    accuracy delta of fp32."""
    _build()
    prefix, x, expected = _export_quantized_tiny(tmp_path)
    x.tofile(prefix + ".in0.bin")
    r = _run_cli_on_chip(prefix)
    result = json.loads(r.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(result["outputs"][0]["f32_sum"],
                               float(expected.sum()), rtol=1e-3)
    out = np.fromfile(prefix + ".out0.bin", np.float32).reshape(
        expected.shape)
    np.testing.assert_allclose(out, expected, rtol=1e-3, atol=1e-4)
