"""Amalgamated single-file predict build (reference:
amalgamation/amalgamation.py + mxnet_predict0.cc — one translation
unit carrying the whole predict-only native runtime).

Validated the way a deployment uses it: regenerate + compile the
single file, link the same C++ client the split build uses, and run
the predict flow end-to-end; the record-reader symbols must ride in
the same library.
"""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from test_c_predict_api import _CPP_MAIN, _build_artifacts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def amalgamated_lib():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "amalgamation",
                                      "amalgamation.py"), "--build"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    lib = os.path.join(REPO, "build", "native", "libmxtpu_predict0.so")
    assert os.path.exists(lib)
    return lib


def test_amalgamation_single_file_and_symbols(amalgamated_lib):
    cc = os.path.join(REPO, "amalgamation", "mxnet_tpu_predict0.cc")
    assert os.path.exists(cc)
    # both the predict ABI and the recordio reader live in the one .so
    dll = ctypes.CDLL(amalgamated_lib)
    for sym in ("MXPredCreate", "MXPredForward", "MXPredGetOutput",
                "MXPredFree", "rio_open", "rio_read", "rio_write"):
        assert hasattr(dll, sym), sym


def test_amalgamated_predict_end_to_end(tmp_path, amalgamated_lib):
    json_path, params_path, expect = _build_artifacts(tmp_path)
    main_cc = tmp_path / "main.cc"
    main_cc.write_text(_CPP_MAIN)
    exe = str(tmp_path / "predict_amalg")
    r = subprocess.run(
        ["g++", "-O1", "-std=c++17", str(main_cc), "-o", exe,
         "-I", os.path.join(REPO, "cpp-package", "include"),
         "-L", os.path.dirname(amalgamated_lib), "-lmxtpu_predict0",
         "-Wl,-rpath," + os.path.dirname(amalgamated_lib)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr

    env = dict(os.environ)
    site = [p for p in sys.path if p.endswith("site-packages")]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + site +
                                        [env.get("PYTHONPATH", "")])
    env.pop("PYTHONHOME", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([exe, json_path, params_path], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0].strip() == "shape 2 3"
    got = np.array([float(v) for v in lines[1].split()]).reshape(2, 3)
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)
