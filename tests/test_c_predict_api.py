"""Native C predict ABI: build, link a C++ client, run end-to-end.

Reference: include/mxnet/c_predict_api.h (the standalone inference ABI
every foreign binding links) — validated here the way a deployment
would use it: a real C++ program compiled against
cpp-package/include/mxnet_tpu_cpp/predictor.hpp, linked to
build/native/libmxtpu_predict.so, run as a separate process.
"""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CPP_MAIN = r"""
#include <cstdio>
#include <fstream>
#include <sstream>
#include "mxnet_tpu_cpp/predictor.hpp"

static std::string slurp(const char* path) {
  std::ifstream f(path, std::ios::binary);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

int main(int argc, char** argv) {
  std::string json = slurp(argv[1]);
  std::string params = slurp(argv[2]);
  std::map<std::string, std::vector<uint32_t>> shapes{{"data", {2, 4}}};
  mxnet_tpu_cpp::Predictor pred(json, params, shapes, /*dev_type=*/1);
  std::vector<float> in(8);
  for (int i = 0; i < 8; ++i) in[i] = 0.25f * i;
  pred.SetInput("data", in);
  pred.Forward();
  auto shape = pred.GetOutputShape(0);
  auto out = pred.GetOutput(0);
  printf("shape %u %u\n", shape[0], shape[1]);
  for (float v : out) printf("%.6f ", v);
  printf("\n");
  return 0;
}
"""


def _build_artifacts(tmp_path):
    # model: y = softmax(FC(x)) with fixed weights
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    sym = mx.sym.softmax(fc, name="prob")
    rng = np.random.RandomState(0)
    w = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    params = {"arg:fc_weight": mx.nd.array(w), "arg:fc_bias": mx.nd.array(b)}
    json_path = os.path.join(str(tmp_path), "model.json")
    params_path = os.path.join(str(tmp_path), "model.params")
    with open(json_path, "w") as f:
        f.write(sym.tojson())
    mx.nd.save(params_path, params)
    x = np.arange(8, dtype=np.float32).reshape(2, 4) * 0.25
    logits = x @ w.T + b
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    expect = e / e.sum(axis=1, keepdims=True)
    return json_path, params_path, expect


@pytest.fixture(scope="module")
def native_lib():
    lib = os.path.join(REPO, "build", "native", "libmxtpu_predict.so")
    r = subprocess.run(["make", "-C", os.path.join(REPO, "src", "native")],
                      capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert os.path.exists(lib)
    return lib


def test_c_predict_end_to_end(tmp_path, native_lib):
    json_path, params_path, expect = _build_artifacts(tmp_path)
    main_cc = tmp_path / "main.cc"
    main_cc.write_text(_CPP_MAIN)
    exe = str(tmp_path / "predict_test")
    r = subprocess.run(
        ["g++", "-O1", "-std=c++17", str(main_cc), "-o", exe,
         "-I", os.path.join(REPO, "cpp-package", "include"),
         "-L", os.path.dirname(native_lib), "-lmxtpu_predict",
         "-Wl,-rpath," + os.path.dirname(native_lib)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr

    env = dict(os.environ)
    site = [p for p in sys.path if p.endswith("site-packages")]
    # the embedded libpython uses its own stdlib home; the venv's
    # site-packages (jax etc.) + the repo ride in via PYTHONPATH
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


def test_perl_binding_predicts(tmp_path, native_lib):
    """perl-package proof (reference perl-package/ AI::MXNet analog):
    the XS binding over the predict ABI builds with core-Perl tooling
    only and reproduces the Python-side softmax probabilities."""
    perl = shutil.which("perl")
    if perl is None:
        pytest.skip("no perl interpreter")
    pkg = os.path.join(REPO, "perl-package", "AI-MXNetTPU")
    r = subprocess.run([perl, os.path.join(pkg, "build.pl")],
                       capture_output=True, text=True)
    if r.returncode != 0 and "ExtUtils" in (r.stderr or ""):
        pytest.skip("perl lacks ExtUtils::ParseXS: " + r.stderr[:200])
    assert r.returncode == 0, r.stdout + r.stderr

    json_path, params_path, expect = _build_artifacts(tmp_path)
    script = tmp_path / "predict.pl"
    script.write_text("""
use strict; use warnings;
use AI::MXNetTPU;
my ($json_path, $params_path) = @ARGV;
local $/;
open(my $jf, "<", $json_path) or die $!;  my $json = <$jf>;
open(my $pf, "<:raw", $params_path) or die $!;  my $params = <$pf>;
my $pred = AI::MXNetTPU::Predictor->new(
    symbol_json => $json, params => $params,
    input_name => "data", input_shape => [2, 4]);
my @out = $pred->predict(map { $_ * 0.25 } 0 .. 7);
print join(" ", map { sprintf("%.6f", $_) } @out), "\\n";
""")
    env = _perl_env()
    r = subprocess.run(
        [perl, "-I", os.path.join(pkg, "lib"),
         "-I", os.path.join(pkg, "blib", "arch"),
         str(script), json_path, params_path],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    got = np.array([float(v) for v in r.stdout.split()]).reshape(2, 3)
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)


def _perl_env():
    env = dict(os.environ)
    site = [p for p in sys.path if p.endswith("site-packages")]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + site +
                                        [env.get("PYTHONPATH", "")])
    env.pop("PYTHONHOME", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env
