"""General C ABI: build the library, compile C++ clients against the
generated op wrappers, train a model from C++.

Reference: include/mxnet/c_api.h (NDArray CRUD, imperative invoke,
autograd, symbol/executor) +
cpp-package/scripts/OpWrapperGenerator.py (generated op.h).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    env = dict(os.environ)
    site = [p for p in sys.path if p.endswith("site-packages")]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + site +
                                        [env.get("PYTHONPATH", "")])
    env.pop("PYTHONHOME", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.fixture(scope="module")
def c_api_lib():
    lib = os.path.join(REPO, "build", "native", "libmxtpu_c_api.so")
    r = subprocess.run(["make", "-C", os.path.join(REPO, "src", "native")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert os.path.exists(lib)
    return lib


def _compile(tmp_path, src_path, c_api_lib, name):
    exe = str(tmp_path / name)
    r = subprocess.run(
        ["g++", "-O1", "-std=c++17", src_path, "-o", exe,
         "-I", os.path.join(REPO, "cpp-package", "include"),
         "-I", os.path.join(REPO, "include"),
         "-L", os.path.dirname(c_api_lib), "-lmxtpu_c_api",
         "-Wl,-rpath," + os.path.dirname(c_api_lib)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    return exe


def test_cpp_client_trains_linear_model(tmp_path, c_api_lib):
    """The VERDICT round-3 acceptance: a C++ client trains a linear
    model end-to-end through the ABI (autograd + generated wrappers +
    in-place sgd_update)."""
    src = os.path.join(REPO, "examples", "cpp", "train_linear.cc")
    exe = _compile(tmp_path, src, c_api_lib, "train_linear")
    r = subprocess.run([exe], env=_child_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TRAIN OK" in r.stdout, r.stdout
    w = [float(v) for v in
         [l for l in r.stdout.splitlines() if l.startswith("w ")][0]
         .split()[1:]]
    np.testing.assert_allclose(w, [2.0, -1.0, 0.5], atol=0.05)


_CRUD_MAIN = r"""
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>
#include "mxnet_tpu_cpp/ndarray.hpp"
#include "mxnet_tpu_cpp/op.h"

using namespace mxnet_tpu_cpp;

int main(int argc, char** argv) {
  // CRUD + dtype + shape
  NDArray a({2, 3});
  std::vector<float> vals = {1, 2, 3, 4, 5, 6};
  a.CopyFrom(vals);
  auto shp = a.Shape();
  std::printf("shape %u %u\n", shp[0], shp[1]);
  int dt = -1;
  Check(MXNDArrayGetDType(a.handle(), &dt));
  std::printf("dtype %d\n", dt);

  // op discovery
  uint32_t n_ops = 0;
  const char** names = nullptr;
  Check(MXListAllOpNames(&n_ops, &names));
  std::printf("ops %u\n", n_ops);
  const char* doc = nullptr;
  uint32_t n_attrs = 0;
  const char **attr_names = nullptr, **attr_defaults = nullptr;
  int n_out = 0;
  Check(MXOpGetInfo("Convolution", &doc, &n_attrs, &attr_names,
                    &attr_defaults, &n_out));
  bool has_kernel = false;
  for (uint32_t i = 0; i < n_attrs; ++i)
    if (std::strcmp(attr_names[i], "kernel") == 0) has_kernel = true;
  std::printf("conv_has_kernel %d\n", has_kernel ? 1 : 0);

  // imperative compute via generated wrappers
  NDArray b = op::relu(op::negative(a));
  auto out = b.CopyTo();
  std::printf("relu_neg %.1f %.1f\n", out[0], out[5]);

  // save / load round trip
  const char* fname = argv[1];
  NDArrayHandle hs[1] = {a.handle()};
  const char* ns[1] = {"a"};
  Check(MXNDArraySave(fname, 1, hs, ns));
  uint32_t n_loaded = 0, n_names = 0;
  NDArrayHandle* loaded = nullptr;
  const char** lnames = nullptr;
  Check(MXNDArrayLoad(fname, &n_loaded, &loaded, &n_names, &lnames));
  NDArray back = NDArray::FromHandle(loaded[0]);
  auto bv = back.CopyTo();
  std::printf("loaded %u %s %.1f\n", n_loaded, lnames[0], bv[3]);

  // symbol + executor path
  std::string json = argv[2];
  SymbolHandle sym = nullptr;
  Check(MXSymbolCreateFromJSON(json.c_str(), &sym));
  uint32_t n_args = 0;
  const char** arg_names = nullptr;
  Check(MXSymbolListArguments(sym, &n_args, &arg_names));
  std::printf("sym_args %u\n", n_args);
  MXSymbolFree(sym);
  std::printf("CRUD OK\n");
  return 0;
}
"""


def test_cpp_crud_ops_serialization_symbol(tmp_path, c_api_lib):
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    json_path = str(tmp_path / "m.json")
    with open(json_path, "w") as f:
        f.write(fc.tojson())
    src = tmp_path / "crud.cc"
    src.write_text(_CRUD_MAIN)
    exe = _compile(tmp_path, str(src), c_api_lib, "crud")
    save_path = str(tmp_path / "arrs.ndarray")
    with open(json_path) as f:
        json_arg = f.read()
    r = subprocess.run([exe, save_path, json_arg], env=_child_env(),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    out = dict(l.split(None, 1) for l in r.stdout.strip().splitlines()
               if " " in l)
    assert out["shape"] == "2 3"
    assert out["dtype"] == "0"
    assert int(out["ops"].split()[0]) > 300
    assert out["conv_has_kernel"] == "1"
    assert out["relu_neg"].split() == ["-0.0", "-0.0"] or \
        [float(v) for v in out["relu_neg"].split()] == [0.0, 0.0]
    assert out["loaded"].split() == ["1", "a", "4.0"]
    assert out["sym_args"] == "3"
    assert "CRUD OK" in r.stdout


def _write_mnist_idx(tmp_path, n=1024):
    """Synthetic-but-learnable MNIST idx files: each class lights a
    class-keyed block; an MLP separates them to ~1.0 accuracy."""
    import struct
    rng = np.random.RandomState(0)
    labels = (np.arange(n) % 10).astype(np.uint8)
    imgs = np.zeros((n, 28, 28), np.uint8)
    for i, c in enumerate(labels):
        img = rng.randint(0, 60, (28, 28)).astype(np.uint8)
        r, col = divmod(int(c), 5)
        img[r * 13 + 2:r * 13 + 12, col * 5 + 2:col * 5 + 6] = 255
        imgs[i] = img
    img_path = str(tmp_path / "imgs.idx")
    lbl_path = str(tmp_path / "lbls.idx")
    with open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(imgs.tobytes())
    with open(lbl_path, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())
    return img_path, lbl_path


def test_cpp_mlp_trains_via_full_abi(tmp_path, c_api_lib):
    """VERDICT r4 item 4 acceptance: a C++ MNIST MLP trains to >0.9
    accuracy through the broadened ABI — DataIter (MNISTIter), kvstore
    push/pull, optimizer wrapper, profiler config/state/dump."""
    img_path, lbl_path = _write_mnist_idx(tmp_path)
    src = os.path.join(REPO, "examples", "cpp", "train_mnist_mlp.cc")
    exe = _compile(tmp_path, src, c_api_lib, "train_mnist_mlp")
    profile = str(tmp_path / "profile.json")
    r = subprocess.run([exe, img_path, lbl_path, profile],
                       env=_child_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TRAIN OK" in r.stdout, r.stdout
    assert "kvstore type=local rank=0 size=1" in r.stdout, r.stdout
    assert os.path.exists(profile)
    with open(profile) as f:
        assert "traceEvents" in f.read()


def test_c_api_data_iter_surface(tmp_path, c_api_lib):
    """MXListDataIters + CSVIter through ctypes (binding-level check of
    the io ABI, independent of the C++ wrappers)."""
    import ctypes
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p
    n = ctypes.c_uint32()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXListDataIters(ctypes.byref(n), ctypes.byref(names)) == 0
    listed = {names[i].decode() for i in range(n.value)}
    assert {"ImageRecordIter", "MNISTIter", "CSVIter"} <= listed


def test_c_api_batch2_surfaces(tmp_path, c_api_lib):
    """Batch-2 ABI functions at the ctypes level: version/device/seed,
    NDArray views + context/storage queries, symbol listings and attrs,
    engine bulk size, profiler pause + aggregate stats."""
    import ctypes
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p

    v = ctypes.c_int()
    assert lib.MXGetVersion(ctypes.byref(v)) == 0 and v.value == 100
    n = ctypes.c_int()
    assert lib.MXGetGPUCount(ctypes.byref(n)) == 0 and n.value >= 0
    assert lib.MXRandomSeed(7) == 0
    prev = ctypes.c_int()
    assert lib.MXEngineSetBulkSize(16, ctypes.byref(prev)) == 0

    # NDArray (3, 4) zeros -> slice/at/reshape/context/storage
    shape = (ctypes.c_uint32 * 2)(3, 4)
    h = ctypes.c_void_p()
    assert lib.MXNDArrayCreate(shape, 2, 0, b"cpu", 0,
                               ctypes.byref(h)) == 0
    out = ctypes.c_void_p()
    assert lib.MXNDArraySlice(h, 1, 3, ctypes.byref(out)) == 0
    ndim = ctypes.c_uint32()
    dims = (ctypes.c_uint32 * 32)()
    assert lib.MXNDArrayGetShape(out, ctypes.byref(ndim), dims) == 0
    assert (ndim.value, dims[0], dims[1]) == (2, 2, 4)
    lib.MXNDArrayFree(out)
    assert lib.MXNDArrayAt(h, 0, ctypes.byref(out)) == 0
    assert lib.MXNDArrayGetShape(out, ctypes.byref(ndim), dims) == 0
    assert (ndim.value, dims[0]) == (1, 4)
    lib.MXNDArrayFree(out)
    rdims = (ctypes.c_int * 2)(4, 3)
    assert lib.MXNDArrayReshape(h, 2, rdims, ctypes.byref(out)) == 0
    assert lib.MXNDArrayGetShape(out, ctypes.byref(ndim), dims) == 0
    assert (dims[0], dims[1]) == (4, 3)
    lib.MXNDArrayFree(out)
    dt = ctypes.c_int()
    di = ctypes.c_int()
    assert lib.MXNDArrayGetContext(h, ctypes.byref(dt),
                                   ctypes.byref(di)) == 0
    assert dt.value in (1, 2, 3) and di.value == 0
    st = ctypes.c_int()
    assert lib.MXNDArrayGetStorageType(h, ctypes.byref(st)) == 0
    assert st.value == 0
    assert lib.MXNDArrayWaitAll() == 0
    lib.MXNDArrayFree(h)

    # symbol listings + attr
    import mxnet_tpu as mx2
    bn = mx2.sym.BatchNorm(mx2.sym.var("data"), name="bn0")
    sym = ctypes.c_void_p()
    assert lib.MXSymbolCreateFromJSON(bn.tojson().encode(),
                                      ctypes.byref(sym)) == 0
    cnt = ctypes.c_uint32()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXSymbolListOutputs(sym, ctypes.byref(cnt),
                                   ctypes.byref(names)) == 0
    outs = [names[i].decode() for i in range(cnt.value)]
    assert outs and outs[0].startswith("bn0")
    assert lib.MXSymbolListAuxiliaryStates(sym, ctypes.byref(cnt),
                                           ctypes.byref(names)) == 0
    aux = [names[i].decode() for i in range(cnt.value)]
    assert "bn0_moving_mean" in aux

    # profiler pause + aggregate stats string
    assert lib.MXSetProcessProfilerState(1) == 0
    assert lib.MXProcessProfilePause(1) == 0
    assert lib.MXProcessProfilePause(0) == 0
    assert lib.MXSetProcessProfilerState(0) == 0
    s = ctypes.c_char_p()
    assert lib.MXAggregateProfileStatsPrint(ctypes.byref(s), 0) == 0
    assert s.value is not None


_CPP_EXEC_MAIN = r"""
// Symbol+Executor C++ training path (executor.hpp over the ABI):
// loads a LinearRegressionOutput topology from JSON, simple-binds with
// example inputs, runs forward/backward/SGD on executor args.
#include <cstdio>
#include <fstream>
#include <sstream>
#include <vector>

#include "mxnet_tpu_cpp/MxNetCpp.h"

using namespace mxnet_tpu_cpp;  // NOLINT

int main(int argc, char** argv) {
  std::ifstream f(argv[1]);
  std::stringstream ss;
  ss << f.rdbuf();
  Symbol sym = Symbol::FromJSON(ss.str());

  const uint32_t kN = 32, kD = 3;
  NDArray x({kN, kD}), y({kN, 1});
  std::vector<float> xs(kN * kD), ys(kN);
  unsigned seed = 99;
  auto frand = [&seed]() {
    seed = seed * 1103515245u + 12345u;
    return ((seed >> 16) & 0x7fff) / 32768.0f - 0.5f;
  };
  const float w_true[kD] = {1.5f, -2.0f, 0.5f};
  for (uint32_t i = 0; i < kN; ++i) {
    float dot = 0.0f;
    for (uint32_t j = 0; j < kD; ++j) {
      xs[i * kD + j] = frand();
      dot += xs[i * kD + j] * w_true[j];
    }
    ys[i] = dot;
  }
  x.CopyFrom(xs);
  y.CopyFrom(ys);

  Executor exec(sym, {"data", "lro_label"}, {&x, &y});
  {
    // simple_bind takes shapes from the examples; values are fed by
    // writing the executor's own arg arrays (arg_dict["data"][:] = x)
    NDArray xd = exec.Arg("data");
    xd.CopyFrom(xs);
    NDArray yd = exec.Arg("lro_label");
    yd.CopyFrom(ys);
    NDArray w = exec.Arg("fc_weight");
    std::vector<float> zeros(w.Size(), 0.0f);
    w.CopyFrom(zeros);
    NDArray b = exec.Arg("fc_bias");
    std::vector<float> bz(b.Size(), 0.0f);
    b.CopyFrom(bz);
  }
  SGDOptimizer opt(0.4f);
  for (int step = 0; step < 80; ++step) {
    exec.Forward(true);
    exec.Backward();
    NDArray w = exec.Arg("fc_weight");
    NDArray g = exec.Grad("fc_weight");
    opt.Update(0, &w, g);
    NDArray b = exec.Arg("fc_bias");
    NDArray gb = exec.Grad("fc_bias");
    opt.Update(1, &b, gb);
  }
  std::vector<float> w = exec.Arg("fc_weight").CopyTo();
  std::printf("w %.3f %.3f %.3f\n", w[0], w[1], w[2]);
  for (uint32_t j = 0; j < kD; ++j) {
    float err = w[j] - w_true[j];
    if (err < 0) err = -err;
    if (err > 0.1f) { std::printf("EXEC TRAIN FAILED\n"); return 1; }
  }
  std::printf("EXEC TRAIN OK\n");
  return 0;
}
"""


def test_cpp_executor_trains_from_symbol_json(tmp_path, c_api_lib):
    """The Symbol/Executor C++ wrappers (executor.hpp) train a model
    loaded from JSON — the reference cpp-package's executor.h path."""
    import mxnet_tpu as mx2
    data = mx2.sym.Variable("data")
    fc = mx2.sym.FullyConnected(data, name="fc", num_hidden=1)
    net = mx2.sym.LinearRegressionOutput(fc, name="lro")
    json_path = str(tmp_path / "lin.json")
    with open(json_path, "w") as f:
        f.write(net.tojson())
    main_cc = tmp_path / "exec_main.cc"
    main_cc.write_text(_CPP_EXEC_MAIN)
    exe = _compile(tmp_path, str(main_cc), c_api_lib, "exec_train")
    r = subprocess.run([exe, json_path], env=_child_env(),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "EXEC TRAIN OK" in r.stdout, r.stdout


def test_c_api_batch3_surfaces(tmp_path, c_api_lib):
    """Batch-3 ABI: profiler objects, raw-bytes NDArray round-trip,
    device-side copy, kvstore pushpull, executor reshape."""
    import ctypes
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p
    lib.MXNDArraySaveRawBytes.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
        ctypes.POINTER(ctypes.c_char_p)]
    lib.MXNDArrayLoadFromRawBytes.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_void_p)]

    # profiler objects
    dom = ctypes.c_void_p()
    assert lib.MXProfileCreateDomain(b"dom", ctypes.byref(dom)) == 0
    task = ctypes.c_void_p()
    assert lib.MXProfileCreateTask(dom, b"work", ctypes.byref(task)) == 0
    assert lib.MXSetProcessProfilerState(1) == 0
    assert lib.MXProfileDurationStart(task) == 0
    assert lib.MXProfileDurationStop(task) == 0
    ctr = ctypes.c_void_p()
    assert lib.MXProfileCreateCounter(dom, b"cnt", ctypes.byref(ctr)) == 0
    assert lib.MXProfileSetCounter(ctr, 5) == 0
    assert lib.MXProfileAdjustCounter(ctr, -2) == 0
    assert lib.MXProfileSetMarker(dom, b"mark", b"process") == 0
    assert lib.MXSetProcessProfilerState(0) == 0
    lib.MXProfileDestroyHandle(task)
    lib.MXProfileDestroyHandle(ctr)
    lib.MXProfileDestroyHandle(dom)

    # raw bytes round-trip + copy-from-ndarray
    shape = (ctypes.c_uint32 * 2)(2, 3)
    h = ctypes.c_void_p()
    assert lib.MXNDArrayCreate(shape, 2, 0, b"cpu", 0,
                               ctypes.byref(h)) == 0
    vals = (ctypes.c_float * 6)(*[float(i) for i in range(6)])
    assert lib.MXNDArraySyncCopyFromCPU(h, vals, 6 * 4) == 0
    size = ctypes.c_size_t()
    buf = ctypes.c_char_p()
    assert lib.MXNDArraySaveRawBytes(h, ctypes.byref(size),
                                     ctypes.byref(buf)) == 0
    raw = ctypes.string_at(buf, size.value)
    h2 = ctypes.c_void_p()
    assert lib.MXNDArrayLoadFromRawBytes(raw, len(raw),
                                         ctypes.byref(h2)) == 0
    got = (ctypes.c_float * 6)()
    assert lib.MXNDArraySyncCopyToCPU(h2, got, 6 * 4) == 0
    assert list(got) == [float(i) for i in range(6)]
    h3 = ctypes.c_void_p()
    assert lib.MXNDArrayCreate(shape, 2, 0, b"cpu", 0,
                               ctypes.byref(h3)) == 0
    assert lib.MXNDArraySyncCopyFromNDArray(h3, h2) == 0
    assert lib.MXNDArraySyncCopyToCPU(h3, got, 6 * 4) == 0
    assert list(got) == [float(i) for i in range(6)]

    # kvstore pushpull
    kv = ctypes.c_void_p()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    keys = (ctypes.c_char_p * 1)(b"w")
    arrs = (ctypes.c_void_p * 1)(h.value)
    assert lib.MXKVStoreInit(kv, 1, keys, arrs) == 0
    outs = (ctypes.c_void_p * 1)(h3.value)
    assert lib.MXKVStorePushPull(kv, 1, keys, arrs, outs, 0) == 0
    assert lib.MXKVStoreBarrier(kv) == 0
    lib.MXKVStoreFree(kv)
    for hh in (h, h2, h3):
        lib.MXNDArrayFree(hh)


def test_c_api_symbol_construction(tmp_path, c_api_lib):
    """Graphs built purely through the ABI (CreateVariable /
    CreateAtomicSymbol / Compose) bind and run like JSON-built ones."""
    import ctypes
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p

    data = ctypes.c_void_p()
    assert lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)) == 0
    fc = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"3")
    assert lib.MXSymbolCreateAtomicSymbol(
        b"FullyConnected", 1, keys, vals, b"fc", ctypes.byref(fc)) == 0
    ckeys = (ctypes.c_char_p * 1)(b"data")
    cargs = (ctypes.c_void_p * 1)(data.value)
    assert lib.MXSymbolCompose(fc, b"fc", 1, ckeys, cargs) == 0

    n = ctypes.c_uint32()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXSymbolListArguments(fc, ctypes.byref(n),
                                     ctypes.byref(names)) == 0
    got = [names[i].decode() for i in range(n.value)]
    assert got == ["data", "fc_weight", "fc_bias"], got

    # bind + forward through the executor surface
    shape = (ctypes.c_uint32 * 2)(2, 5)
    x = ctypes.c_void_p()
    assert lib.MXNDArrayCreate(shape, 2, 0, b"cpu", 0,
                               ctypes.byref(x)) == 0
    in_names = (ctypes.c_char_p * 1)(b"data")
    in_arrs = (ctypes.c_void_p * 1)(x.value)
    exe = ctypes.c_void_p()
    assert lib.MXExecutorSimpleBind(fc, 1, in_names, in_arrs,
                                    ctypes.byref(exe)) == 0
    assert lib.MXExecutorForward(exe, 0) == 0
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXExecutorOutputs(exe, ctypes.byref(n),
                                 ctypes.byref(outs)) == 0
    ndim = ctypes.c_uint32()
    dims = (ctypes.c_uint32 * 32)()
    # outs[0] is a bare int; wrap it or ctypes truncates the pointer
    out0 = ctypes.c_void_p(outs[0])
    assert lib.MXNDArrayGetShape(out0, ctypes.byref(ndim), dims) == 0
    assert (dims[0], dims[1]) == (2, 3)
    cp = ctypes.c_void_p()
    assert lib.MXSymbolCopy(fc, ctypes.byref(cp)) == 0
    lib.MXExecutorFree(exe)
    for h in (data, fc, cp, x):
        lib.MXNDArrayFree(h)


_CPP_SYMBUILD_MAIN = r"""
// Build a graph in C++ via Symbol::Variable/Atomic/Compose (no JSON),
// then bind + forward through Executor.
#include <cstdio>
#include "mxnet_tpu_cpp/MxNetCpp.h"

using namespace mxnet_tpu_cpp;  // NOLINT

int main() {
  Symbol data = Symbol::Variable("data");
  Symbol w = Symbol::Variable("fc_weight");
  // generated symbolic wrapper (op::sym namespace); the optional bias
  // input stays a free auto-variable
  Symbol fc = op::sym::FullyConnected(data, w,
                                      {{"num_hidden", "4"}}, "fc");
  auto args = fc.ListArguments();
  if (args.size() != 3) { std::printf("BAD ARGS\n"); return 1; }
  NDArray x({2, 6});
  std::vector<float> vals(12, 1.0f);
  x.CopyFrom(vals);
  Executor exec(fc, {"data"}, {&x});
  exec.Forward(false);
  auto outs = exec.Outputs();
  auto shp = outs[0].Shape();
  std::printf("out %u %u\n", shp[0], shp[1]);
  std::printf("SYMBUILD OK\n");
  return 0;
}
"""


def test_cpp_symbol_building(tmp_path, c_api_lib):
    """cpp-package builds graphs natively (Variable/Atomic/Compose)."""
    src = tmp_path / "symbuild.cc"
    src.write_text(_CPP_SYMBUILD_MAIN)
    exe = _compile(tmp_path, str(src), c_api_lib, "symbuild")
    r = subprocess.run([exe], env=_child_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "out 2 4" in r.stdout and "SYMBUILD OK" in r.stdout, r.stdout


def test_c_api_batch5_ndarray_autograd_cachedop(tmp_path, c_api_lib):
    """Batch-5 ABI part 1: NDArray extras (CreateEx/None/Detach/grad/
    Reshape64/GetData/LoadFromBuffer), sparse create + accessors +
    format check, autograd state + BackwardEx, CachedOp."""
    import ctypes
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p

    # CreateEx (dev_type 1 = cpu) + GetData snapshot
    shape = (ctypes.c_uint32 * 2)(2, 3)
    h = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0,
                                 ctypes.byref(h)) == 0
    vals = (ctypes.c_float * 6)(*[float(i) for i in range(6)])
    assert lib.MXNDArraySyncCopyFromCPU(h, vals, 6 * 4) == 0
    assert lib.MXNDArrayWaitToWrite(h) == 0
    p = ctypes.c_void_p()
    assert lib.MXNDArrayGetData(h, ctypes.byref(p)) == 0
    snap = ctypes.cast(p, ctypes.POINTER(ctypes.c_float * 6)).contents
    assert list(snap) == [float(i) for i in range(6)]

    # CreateNone
    none_h = ctypes.c_void_p()
    assert lib.MXNDArrayCreateNone(ctypes.byref(none_h)) == 0
    ndim = ctypes.c_uint32()
    oshape = (ctypes.c_uint32 * 32)()
    assert lib.MXNDArrayGetShape(none_h, ctypes.byref(ndim), oshape) == 0
    assert ndim.value == 1 and oshape[0] == 0
    lib.MXNDArrayFree(none_h)

    # Reshape64: specials 0 (copy) and -1 (infer), reverse from right
    dims = (ctypes.c_int64 * 2)(3, -1)
    r1 = ctypes.c_void_p()
    assert lib.MXNDArrayReshape64(h, 2, dims, 0, ctypes.byref(r1)) == 0
    assert lib.MXNDArrayGetShape(r1, ctypes.byref(ndim), oshape) == 0
    assert (ndim.value, oshape[0], oshape[1]) == (2, 3, 2)
    lib.MXNDArrayFree(r1)

    # grad: none attached -> NULL; Detach returns a new handle
    g = ctypes.c_void_p(1234)
    assert lib.MXNDArrayGetGrad(h, ctypes.byref(g)) == 0
    assert not g.value
    d = ctypes.c_void_p()
    assert lib.MXNDArrayDetach(h, ctypes.byref(d)) == 0
    lib.MXNDArrayFree(d)

    # LoadFromBuffer round-trip via MXNDArraySave bytes
    fname = str(tmp_path / "arrs.params").encode()
    keys = (ctypes.c_char_p * 1)(b"w")
    arrs = (ctypes.c_void_p * 1)(h.value)
    assert lib.MXNDArraySave(fname, 1, arrs, keys) == 0
    raw = open(fname, "rb").read()
    out_num = ctypes.c_uint32()
    out_arrs = ctypes.POINTER(ctypes.c_void_p)()
    name_num = ctypes.c_uint32()
    out_names = ctypes.POINTER(ctypes.c_char_p)()
    lib.MXNDArrayLoadFromBuffer.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_void_p)),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p))]
    assert lib.MXNDArrayLoadFromBuffer(
        raw, len(raw), ctypes.byref(out_num), ctypes.byref(out_arrs),
        ctypes.byref(name_num), ctypes.byref(out_names)) == 0
    assert out_num.value == 1 and out_names[0] == b"w"
    got = (ctypes.c_float * 6)()
    assert lib.MXNDArraySyncCopyToCPU(ctypes.c_void_p(out_arrs[0]), got, 6 * 4) == 0
    assert list(got) == [float(i) for i in range(6)]
    lib.MXNDArrayFree(ctypes.c_void_p(out_arrs[0]))

    # sparse: rsp from data+indices, accessors, format check
    dshape = (ctypes.c_uint32 * 2)(2, 3)
    dh = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(dshape, 2, 1, 0, 0, 0,
                                 ctypes.byref(dh)) == 0
    dv = (ctypes.c_float * 6)(*[1.0] * 6)
    assert lib.MXNDArraySyncCopyFromCPU(dh, dv, 6 * 4) == 0
    # indices are int32 by policy (ndarray/sparse.py int64->int32 with
    # bounds check; jax x64 is off)
    ishape = (ctypes.c_uint32 * 1)(2)
    ih = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(ishape, 1, 1, 0, 0, 4,
                                 ctypes.byref(ih)) == 0
    iv = (ctypes.c_int32 * 2)(0, 3)
    assert lib.MXNDArraySyncCopyFromCPU(ih, iv, 2 * 4) == 0
    fshape = (ctypes.c_uint32 * 2)(5, 3)
    aux = (ctypes.c_void_p * 1)(ih.value)
    sp = ctypes.c_void_p()
    assert lib.MXNDArrayCreateSparseEx(1, fshape, 2, dh, 1, aux,
                                       ctypes.byref(sp)) == 0
    st = ctypes.c_int()
    assert lib.MXNDArrayGetStorageType(sp, ctypes.byref(st)) == 0
    assert st.value == 1
    assert lib.MXNDArraySyncCheckFormat(sp, 1) == 0
    av = ctypes.c_void_p()
    assert lib.MXNDArrayGetAuxNDArray(sp, 0, ctypes.byref(av)) == 0
    at = ctypes.c_int()
    assert lib.MXNDArrayGetAuxType(sp, 0, ctypes.byref(at)) == 0
    assert at.value == 4  # int32 indices (framework-wide sparse policy)
    dn = ctypes.c_void_p()
    assert lib.MXNDArrayGetDataNDArray(sp, ctypes.byref(dn)) == 0
    assert lib.MXNDArrayGetShape(dn, ctypes.byref(ndim), oshape) == 0
    assert (ndim.value, oshape[0], oshape[1]) == (2, 2, 3)
    for hh in (av, dn, sp, dh, ih):
        lib.MXNDArrayFree(hh)

    # bad rsp (indices out of bounds) must fail the full check
    ih2 = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(ishape, 1, 1, 0, 0, 4,
                                 ctypes.byref(ih2)) == 0
    bad = (ctypes.c_int32 * 2)(0, 99)
    assert lib.MXNDArraySyncCopyFromCPU(ih2, bad, 2 * 4) == 0
    dh2 = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(dshape, 2, 1, 0, 0, 0,
                                 ctypes.byref(dh2)) == 0
    sp2 = ctypes.c_void_p()
    assert lib.MXNDArrayCreateSparseEx(1, fshape, 2, dh2, 1,
                                       (ctypes.c_void_p * 1)(ih2.value),
                                       ctypes.byref(sp2)) == 0
    assert lib.MXNDArraySyncCheckFormat(sp2, 1) == -1
    assert b"out of bounds" in lib.MXGetLastError()
    for hh in (sp2, dh2, ih2):
        lib.MXNDArrayFree(hh)

    # autograd state + BackwardEx with explicit variables
    cur = ctypes.c_int(-1)
    assert lib.MXAutogradIsRecording(ctypes.byref(cur)) == 0
    assert cur.value == 0
    assert lib.MXAutogradIsTraining(ctypes.byref(cur)) == 0
    prev = ctypes.c_int(-1)
    assert lib.MXAutogradSetIsTraining(1, ctypes.byref(prev)) == 0
    assert lib.MXAutogradIsTraining(ctypes.byref(cur)) == 0
    assert cur.value == 1
    assert lib.MXAutogradSetIsTraining(prev.value, None) == 0

    x = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0,
                                 ctypes.byref(x)) == 0
    assert lib.MXNDArraySyncCopyFromCPU(x, vals, 6 * 4) == 0
    assert lib.MXAutogradMarkVariables(1, (ctypes.c_void_p * 1)(x.value)) \
        == 0
    assert lib.MXAutogradSetIsRecording(1, ctypes.byref(prev)) == 0
    n_out = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXImperativeInvoke(b"square", 1,
                                  (ctypes.c_void_p * 1)(x.value),
                                  ctypes.byref(n_out), ctypes.byref(outs),
                                  0, None, None) == 0
    y = ctypes.c_void_p(outs[0])
    assert lib.MXAutogradSetIsRecording(0, ctypes.byref(prev)) == 0
    grads = ctypes.POINTER(ctypes.c_void_p)()
    stypes = ctypes.POINTER(ctypes.c_int)()
    assert lib.MXAutogradBackwardEx(
        1, (ctypes.c_void_p * 1)(y.value), None, 1,
        (ctypes.c_void_p * 1)(x.value), 0, 0, 1, ctypes.byref(grads),
        ctypes.byref(stypes)) == 0
    gv = (ctypes.c_float * 6)()
    assert lib.MXNDArraySyncCopyToCPU(ctypes.c_void_p(grads[0]), gv, 6 * 4) == 0
    assert list(gv) == [2.0 * v for v in vals]
    assert stypes[0] == 0
    lib.MXNDArrayFree(ctypes.c_void_p(grads[0]))
    lib.MXNDArrayFree(y)

    # CachedOp over relu(x) built from C symbols
    var = ctypes.c_void_p()
    assert lib.MXSymbolCreateVariable(b"data", ctypes.byref(var)) == 0
    act = ctypes.c_void_p()
    akeys = (ctypes.c_char_p * 1)(b"act_type")
    avals = (ctypes.c_char_p * 1)(b"relu")
    assert lib.MXSymbolCreateAtomicSymbol(b"Activation", 1, akeys, avals,
                                          b"act", ctypes.byref(act)) == 0
    assert lib.MXSymbolCompose(act, b"act", 1,
                               (ctypes.c_char_p * 1)(b"data"),
                               (ctypes.c_void_p * 1)(var.value)) == 0
    cop = ctypes.c_void_p()
    assert lib.MXCreateCachedOpEx(act, 0, None, None,
                                  ctypes.byref(cop)) == 0
    neg = (ctypes.c_float * 6)(-1, 2, -3, 4, -5, 6)
    xin = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0,
                                 ctypes.byref(xin)) == 0
    assert lib.MXNDArraySyncCopyFromCPU(xin, neg, 6 * 4) == 0
    on = ctypes.c_int()
    couts = ctypes.POINTER(ctypes.c_void_p)()
    cst = ctypes.POINTER(ctypes.c_int)()
    assert lib.MXInvokeCachedOpEx(cop, 1,
                                  (ctypes.c_void_p * 1)(xin.value),
                                  ctypes.byref(on), ctypes.byref(couts),
                                  ctypes.byref(cst)) == 0
    assert on.value == 1 and cst[0] == 0
    ov = (ctypes.c_float * 6)()
    assert lib.MXNDArraySyncCopyToCPU(ctypes.c_void_p(couts[0]), ov, 6 * 4) == 0
    assert list(ov) == [0, 2, 0, 4, 0, 6]
    lib.MXNDArrayFree(ctypes.c_void_p(couts[0]))
    assert lib.MXFreeCachedOp(cop) == 0
    for hh in (xin, act, var, x, h):
        (lib.MXNDArrayFree if hh in (xin, x, h) else lib.MXSymbolFree)(hh)


def test_c_api_batch5_symbol_breadth(tmp_path, c_api_lib):
    """Batch-5 ABI part 2: symbol file IO, graph walking, infer
    shape/type, creator registry, quantization passes."""
    import ctypes
    import mxnet_tpu as mx
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p

    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    act = mx.sym.Activation(fc, act_type="relu", name="act")
    json_path = str(tmp_path / "net.json")
    with open(json_path, "w") as f:
        f.write(act.tojson())

    sym = ctypes.c_void_p()
    assert lib.MXSymbolCreateFromFile(json_path.encode(),
                                      ctypes.byref(sym)) == 0
    out_path = str(tmp_path / "net2.json")
    assert lib.MXSymbolSaveToFile(sym, out_path.encode()) == 0
    assert mx.sym.load(out_path).list_arguments() == \
        act.list_arguments()

    # names / outputs / internals / children / inputs
    name = ctypes.c_char_p()
    ok = ctypes.c_int()
    assert lib.MXSymbolGetName(sym, ctypes.byref(name),
                               ctypes.byref(ok)) == 0
    assert ok.value == 1 and name.value == b"act"
    n_out = ctypes.c_uint32()
    assert lib.MXSymbolGetNumOutputs(sym, ctypes.byref(n_out)) == 0
    assert n_out.value == 1
    o0 = ctypes.c_void_p()
    assert lib.MXSymbolGetOutput(sym, 0, ctypes.byref(o0)) == 0
    internals = ctypes.c_void_p()
    assert lib.MXSymbolGetInternals(sym, ctypes.byref(internals)) == 0
    n_int = ctypes.c_uint32()
    names_p = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXSymbolListOutputs(internals, ctypes.byref(n_int),
                                   ctypes.byref(names_p)) == 0
    assert n_int.value >= 2  # fc_output + act_output at least
    children = ctypes.c_void_p()
    assert lib.MXSymbolGetChildren(sym, ctypes.byref(children)) == 0
    inputs = ctypes.POINTER(ctypes.c_void_p)()
    n_in = ctypes.c_int()
    assert lib.MXSymbolGetInputSymbols(sym, ctypes.byref(inputs),
                                       ctypes.byref(n_in)) == 0
    assert n_in.value == 3  # data, fc_weight, fc_bias
    for i in range(n_in.value):
        lib.MXSymbolFree(ctypes.c_void_p(inputs[i]))

    # attrs
    assert lib.MXSymbolSetAttr(sym, b"color", b"blue") == 0
    val = ctypes.c_char_p()
    assert lib.MXSymbolGetAttr(sym, b"color", ctypes.byref(val),
                               ctypes.byref(ok)) == 0
    assert ok.value == 1 and val.value == b"blue"
    n_kv = ctypes.c_uint32()
    kv_p = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXSymbolListAttrShallow(sym, ctypes.byref(n_kv),
                                       ctypes.byref(kv_p)) == 0
    shallow = {kv_p[2 * i]: kv_p[2 * i + 1] for i in range(n_kv.value)}
    assert shallow.get(b"color") == b"blue"
    s = ctypes.c_char_p()
    assert lib.MXSymbolPrint(sym, ctypes.byref(s)) == 0
    assert b"act" in s.value

    # infer shape: data (2, 8) -> out (2, 4); weights inferred
    keys = (ctypes.c_char_p * 1)(b"data")
    ind_ptr = (ctypes.c_uint32 * 2)(0, 2)
    shape_data = (ctypes.c_uint32 * 2)(2, 8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u32pp = ctypes.POINTER(u32p)
    in_sz = ctypes.c_uint32()
    in_nd = u32p()
    in_dat = u32pp()
    out_sz = ctypes.c_uint32()
    out_nd = u32p()
    out_dat = u32pp()
    aux_sz = ctypes.c_uint32()
    aux_nd = u32p()
    aux_dat = u32pp()
    comp = ctypes.c_int()
    assert lib.MXSymbolInferShape(
        sym, 1, keys, ind_ptr, shape_data, ctypes.byref(in_sz),
        ctypes.byref(in_nd), ctypes.byref(in_dat), ctypes.byref(out_sz),
        ctypes.byref(out_nd), ctypes.byref(out_dat), ctypes.byref(aux_sz),
        ctypes.byref(aux_nd), ctypes.byref(aux_dat),
        ctypes.byref(comp)) == 0
    assert comp.value == 1
    assert in_sz.value == 3 and out_sz.value == 1
    assert [out_dat[0][j] for j in range(out_nd[0])] == [2, 4]
    wt = [in_dat[1][j] for j in range(in_nd[1])]
    assert wt == [4, 8]  # fc_weight (num_hidden, input_dim)

    # infer type: float32 propagates
    tdata = (ctypes.c_int * 1)(0)
    i32p = ctypes.POINTER(ctypes.c_int)
    it_sz = ctypes.c_uint32()
    it_d = i32p()
    ot_sz = ctypes.c_uint32()
    ot_d = i32p()
    at_sz = ctypes.c_uint32()
    at_d = i32p()
    assert lib.MXSymbolInferType(
        sym, 1, keys, tdata, ctypes.byref(it_sz), ctypes.byref(it_d),
        ctypes.byref(ot_sz), ctypes.byref(ot_d), ctypes.byref(at_sz),
        ctypes.byref(at_d), ctypes.byref(comp)) == 0
    assert comp.value == 1 and ot_d[0] == 0

    # creator registry
    n_cr = ctypes.c_uint32()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXSymbolListAtomicSymbolCreators(
        ctypes.byref(n_cr), ctypes.byref(creators)) == 0
    assert n_cr.value > 300
    cname = ctypes.c_char_p()
    first = ctypes.c_void_p(creators[0])
    assert lib.MXSymbolGetAtomicSymbolName(first,
                                           ctypes.byref(cname)) == 0
    assert cname.value
    desc = ctypes.c_char_p()
    n_args = ctypes.c_uint32()
    an = ctypes.POINTER(ctypes.c_char_p)()
    ad = ctypes.POINTER(ctypes.c_char_p)()
    kv_var = ctypes.c_char_p()
    assert lib.MXSymbolGetAtomicSymbolInfo(
        first, ctypes.byref(cname), ctypes.byref(desc),
        ctypes.byref(n_args), ctypes.byref(an), ctypes.byref(ad),
        ctypes.byref(kv_var)) == 0

    # quantization passes
    qsym = ctypes.c_void_p()
    assert lib.MXQuantizeSymbol(sym, ctypes.byref(qsym), 0, None,
                                b"int8") == 0
    qn = ctypes.c_char_p()
    assert lib.MXSymbolPrint(qsym, ctypes.byref(qn)) == 0
    assert b"quantize" in qn.value
    lnames = (ctypes.c_char_p * 1)(b"fc")
    mins = (ctypes.c_float * 1)(-1.0)
    maxs = (ctypes.c_float * 1)(1.0)
    cal = ctypes.c_void_p()
    assert lib.MXSetCalibTableToQuantizedSymbol(
        qsym, 1, lnames, mins, maxs, ctypes.byref(cal)) == 0
    for hh in (cal, qsym, children, internals, o0, sym):
        lib.MXSymbolFree(hh)


def test_c_api_batch5_recordio_kv_exec_misc(tmp_path, c_api_lib):
    """Batch-5 ABI part 3: RecordIO reader/writer, kvstore roles +
    updater callback + compression, iter info, explicit-array bind,
    runtime misc."""
    import ctypes
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p
    lib.MXRecordIOWriterWriteRecord.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
    lib.MXRecordIOReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_size_t]

    # RecordIO round trip + seek/tell
    rec_path = str(tmp_path / "t.rec").encode()
    w = ctypes.c_void_p()
    assert lib.MXRecordIOWriterCreate(rec_path, ctypes.byref(w)) == 0
    assert lib.MXRecordIOWriterWriteRecord(w, b"hello", 5) == 0
    pos = ctypes.c_size_t()
    assert lib.MXRecordIOWriterTell(w, ctypes.byref(pos)) == 0
    assert pos.value > 0
    assert lib.MXRecordIOWriterWriteRecord(w, b"worlds!", 7) == 0
    assert lib.MXRecordIOWriterFree(w) == 0
    r = ctypes.c_void_p()
    assert lib.MXRecordIOReaderCreate(rec_path, ctypes.byref(r)) == 0
    buf = ctypes.c_char_p()
    size = ctypes.c_size_t()
    assert lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                          ctypes.byref(size)) == 0
    assert ctypes.string_at(buf, size.value) == b"hello"
    assert lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                          ctypes.byref(size)) == 0
    assert ctypes.string_at(buf, size.value) == b"worlds!"
    assert lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                          ctypes.byref(size)) == 0
    assert size.value == 0  # EOF
    assert lib.MXRecordIOReaderSeek(r, 0) == 0
    assert lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                          ctypes.byref(size)) == 0
    assert ctypes.string_at(buf, size.value) == b"hello"
    assert lib.MXRecordIOReaderFree(r) == 0

    # kvstore roles (no env role set -> worker)
    ret = ctypes.c_int(-1)
    assert lib.MXKVStoreIsWorkerNode(ctypes.byref(ret)) == 0
    assert ret.value == 1
    assert lib.MXKVStoreIsServerNode(ctypes.byref(ret)) == 0
    assert ret.value == 0
    assert lib.MXKVStoreIsSchedulerNode(ctypes.byref(ret)) == 0
    assert ret.value == 0

    # local kv: InitEx/PushEx/PullEx aliases + updater callback +
    # compression + dead-node + barrier flag
    kv = ctypes.c_void_p()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    shape = (ctypes.c_uint32 * 1)(4)
    h = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                 ctypes.byref(h)) == 0
    ones = (ctypes.c_float * 4)(1, 1, 1, 1)
    assert lib.MXNDArraySyncCopyFromCPU(h, ones, 16) == 0
    keys = (ctypes.c_char_p * 1)(b"w")
    arrs = (ctypes.c_void_p * 1)(h.value)
    assert lib.MXKVStoreInitEx(kv, 1, keys, arrs) == 0

    seen = {}
    UPD = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_void_p, ctypes.c_void_p)

    @UPD
    def str_updater(key, recv, local, handle):
        # emulate sgd: local -= 0.5 * recv, through the ABI itself
        seen["key"] = key
        got = (ctypes.c_float * 4)()
        lib.MXNDArraySyncCopyToCPU(ctypes.c_void_p(recv), got, 16)
        cur = (ctypes.c_float * 4)()
        lib.MXNDArraySyncCopyToCPU(ctypes.c_void_p(local), cur, 16)
        upd = (ctypes.c_float * 4)(*[c - 0.5 * g
                                     for c, g in zip(cur, got)])
        lib.MXNDArraySyncCopyFromCPU(ctypes.c_void_p(local), upd, 16)

    assert lib.MXKVStoreSetUpdaterEx(kv, None, str_updater, None) == 0
    g = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                 ctypes.byref(g)) == 0
    twos = (ctypes.c_float * 4)(2, 2, 2, 2)
    assert lib.MXNDArraySyncCopyFromCPU(g, twos, 16) == 0
    assert lib.MXKVStorePushEx(kv, 1, keys,
                               (ctypes.c_void_p * 1)(g.value), 0) == 0
    out = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                 ctypes.byref(out)) == 0
    assert lib.MXKVStorePullEx(kv, 1, keys,
                               (ctypes.c_void_p * 1)(out.value), 0) == 0
    got = (ctypes.c_float * 4)()
    assert lib.MXNDArraySyncCopyToCPU(out, got, 16) == 0
    assert list(got) == [0.0] * 4  # 1 - 0.5*2
    assert seen["key"] == b"w"

    n_dead = ctypes.c_int(-1)
    assert lib.MXKVStoreGetNumDeadNode(kv, 0, ctypes.byref(n_dead),
                                       5) == 0
    assert n_dead.value == 0
    gck = (ctypes.c_char_p * 2)(b"type", b"threshold")
    gcv = (ctypes.c_char_p * 2)(b"2bit", b"0.5")
    assert lib.MXKVStoreSetGradientCompression(kv, 2, gck, gcv) == 0
    assert lib.MXKVStoreSetBarrierBeforeExit(kv, 1) == 0
    lib.MXKVStoreFree(kv)

    # MXInitPSEnv sets env for later kv creation
    ek = (ctypes.c_char_p * 1)(b"MXNET_TPU_TEST_PSENV")
    ev = (ctypes.c_char_p * 1)(b"42")
    assert lib.MXInitPSEnv(1, ek, ev) == 0
    import os
    assert os.environ.get("MXNET_TPU_TEST_PSENV") == "42"

    # iter info
    iname = ctypes.c_char_p()
    idesc = ctypes.c_char_p()
    assert lib.MXDataIterGetIterInfo(b"MNISTIter", ctypes.byref(iname),
                                     ctypes.byref(idesc)) == 0
    assert iname.value == b"MNISTIter"

    # explicit-array bind: y = 2*x via elemwise; grad_req write
    import mxnet_tpu as mx
    x = mx.sym.Variable("x")
    y = mx.sym.square(x, name="sq")
    xa = ctypes.c_void_p()
    s2 = (ctypes.c_uint32 * 1)(3)
    assert lib.MXNDArrayCreateEx(s2, 1, 1, 0, 0, 0,
                                 ctypes.byref(xa)) == 0
    xv = (ctypes.c_float * 3)(1, 2, 3)
    assert lib.MXNDArraySyncCopyFromCPU(xa, xv, 12) == 0
    ga = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(s2, 1, 1, 0, 0, 0,
                                 ctypes.byref(ga)) == 0
    # hand the python symbol to the C side (in-process handle = PyObject*)
    sym_h = ctypes.c_void_p(id(y))
    exe = ctypes.c_void_p()
    reqs = (ctypes.c_uint32 * 1)(1)
    assert lib.MXExecutorBind(sym_h, 1, 0, 1,
                              (ctypes.c_void_p * 1)(xa.value),
                              (ctypes.c_void_p * 1)(ga.value), reqs, 0,
                              None, ctypes.byref(exe)) == 0
    assert lib.MXExecutorForward(exe, 1) == 0
    n_outs = ctypes.c_uint32()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXExecutorOutputs(exe, ctypes.byref(n_outs),
                                 ctypes.byref(outs)) == 0
    yv = (ctypes.c_float * 3)()
    assert lib.MXNDArraySyncCopyToCPU(ctypes.c_void_p(outs[0]), yv,
                                      12) == 0
    assert list(yv) == [1.0, 4.0, 9.0]
    assert lib.MXExecutorBackwardEx(exe, 0, None) == 0
    gv = (ctypes.c_float * 3)()
    assert lib.MXNDArraySyncCopyToCPU(ga, gv, 12) == 0
    assert list(gv) == [2.0, 4.0, 6.0]
    es = ctypes.c_char_p()
    assert lib.MXExecutorPrint(exe, ctypes.byref(es)) == 0
    assert es.value
    osym = ctypes.c_void_p()
    assert lib.MXExecutorGetOptimizedSymbol(exe, ctypes.byref(osym)) == 0
    lib.MXSymbolFree(osym)
    lib.MXExecutorFree(exe)

    # runtime misc
    assert lib.MXNotifyShutdown() == 0
    assert lib.MXSetNumOMPThreads(2) == 0
    assert lib.MXRandomSeedContext(7, 1, 0) == 0
    fm = ctypes.c_int()
    tm = ctypes.c_int()
    assert lib.MXGetGPUMemoryInformation(0, ctypes.byref(fm),
                                         ctypes.byref(tm)) == -1
    assert b"no GPU" in lib.MXGetLastError()
    for hh in (h, g, out, xa, ga):
        lib.MXNDArrayFree(hh)


def test_c_api_batch5b_sparse_dlpack_monitor(tmp_path, c_api_lib):
    """Batch-5b ABI: InvokeEx stypes, sparse pulls, profiler aliases +
    Event, fresh-grad flag, DLPack round-trip, executor monitor
    callback, faithful MXSymbolGrad error."""
    import ctypes
    import mxnet_tpu as mx
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p

    # InvokeEx returns stypes
    shape = (ctypes.c_uint32 * 1)(4)
    h = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                 ctypes.byref(h)) == 0
    v = (ctypes.c_float * 4)(1, -2, 3, -4)
    assert lib.MXNDArraySyncCopyFromCPU(h, v, 16) == 0
    n_out = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    stypes = ctypes.POINTER(ctypes.c_int)()
    assert lib.MXImperativeInvokeEx(b"relu", 1,
                                    (ctypes.c_void_p * 1)(h.value),
                                    ctypes.byref(n_out),
                                    ctypes.byref(outs), 0, None, None,
                                    ctypes.byref(stypes)) == 0
    assert n_out.value == 1 and stypes[0] == 0
    lib.MXNDArrayFree(ctypes.c_void_p(outs[0]))

    # kv pull with sparse flags (dense store; flag exercises the path)
    kv = ctypes.c_void_p()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    keys = (ctypes.c_char_p * 1)(b"w")
    assert lib.MXKVStoreInit(kv, 1, keys,
                             (ctypes.c_void_p * 1)(h.value)) == 0
    out = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                 ctypes.byref(out)) == 0
    assert lib.MXKVStorePullWithSparse(
        kv, 1, keys, (ctypes.c_void_p * 1)(out.value), 0, 1) == 0
    got = (ctypes.c_float * 4)()
    assert lib.MXNDArraySyncCopyToCPU(out, got, 16) == 0
    assert list(got) == [1, -2, 3, -4]
    # row_sparse_pull of rows [0, 2]
    rs = (ctypes.c_uint32 * 1)(2)
    rid = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(rs, 1, 1, 0, 0, 4,
                                 ctypes.byref(rid)) == 0
    ridv = (ctypes.c_int32 * 2)(0, 2)
    assert lib.MXNDArraySyncCopyFromCPU(rid, ridv, 8) == 0
    r2 = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(rs, 1, 1, 0, 0, 0,
                                 ctypes.byref(r2)) == 0
    assert lib.MXKVStorePullRowSparse(
        kv, 1, keys, (ctypes.c_void_p * 1)(r2.value),
        (ctypes.c_void_p * 1)(rid.value), 0) == 0
    g2 = (ctypes.c_float * 2)()
    assert lib.MXNDArraySyncCopyToCPU(r2, g2, 8) == 0
    assert list(g2) == [1.0, 3.0]
    lib.MXKVStoreFree(kv)

    # profiler aliases + Event object
    assert lib.MXSetProfilerState(1) == 0
    ev = ctypes.c_void_p()
    assert lib.MXProfileCreateEvent(b"phase", ctypes.byref(ev)) == 0
    assert lib.MXProfileDurationStart(ev) == 0
    assert lib.MXProfileDurationStop(ev) == 0
    assert lib.MXProfilePause(1) == 0
    assert lib.MXProfilePause(0) == 0
    assert lib.MXSetProfilerState(0) == 0
    lib.MXProfileDestroyHandle(ev)

    # fresh-grad flag
    st = ctypes.c_int(-1)
    assert lib.MXNDArrayGetGradState(h, ctypes.byref(st)) == 0
    assert st.value == 0
    assert lib.MXNDArraySetGradState(h, 1) == 0
    assert lib.MXNDArrayGetGradState(h, ctypes.byref(st)) == 0
    assert st.value == 1

    # DLPack round trip (FromDLPack CONSUMES the tensor — ownership
    # passes to the importer, so no CallDLPackDeleter afterwards)
    dlm = ctypes.c_void_p()
    assert lib.MXNDArrayToDLPack(h, ctypes.byref(dlm)) == 0
    assert dlm.value
    back = ctypes.c_void_p()
    assert lib.MXNDArrayFromDLPack(dlm, ctypes.byref(back)) == 0
    bv = (ctypes.c_float * 4)()
    assert lib.MXNDArraySyncCopyToCPU(back, bv, 16) == 0
    assert list(bv) == [1, -2, 3, -4]
    lib.MXNDArrayFree(back)
    # an UNCONSUMED export is released with CallDLPackDeleter
    dlm2 = ctypes.c_void_p()
    assert lib.MXNDArrayToDLPack(h, ctypes.byref(dlm2)) == 0
    assert lib.MXNDArrayCallDLPackDeleter(dlm2) == 0

    # MXSymbolGrad errors faithfully
    y = mx.sym.square(mx.sym.Variable("x"))
    gsym = ctypes.c_void_p()
    wrt = (ctypes.c_char_p * 1)(b"x")
    assert lib.MXSymbolGrad(ctypes.c_void_p(id(y)), 1, wrt,
                            ctypes.byref(gsym)) == -1
    assert b"deprecated" in lib.MXGetLastError()

    # executor monitor callback sees output names
    xa = ctypes.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 1, 1, 0, 0, 0,
                                 ctypes.byref(xa)) == 0
    assert lib.MXNDArraySyncCopyFromCPU(xa, v, 16) == 0
    exe = ctypes.c_void_p()
    reqs = (ctypes.c_uint32 * 1)(0)
    assert lib.MXExecutorBind(ctypes.c_void_p(id(y)), 1, 0, 1,
                              (ctypes.c_void_p * 1)(xa.value), None,
                              reqs, 0, None, ctypes.byref(exe)) == 0
    seen = []
    MON = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_void_p)

    @MON
    def monitor(name, arr, handle):
        got = (ctypes.c_float * 4)()
        lib.MXNDArraySyncCopyToCPU(ctypes.c_void_p(arr), got, 16)
        seen.append((name, list(got)))

    assert lib.MXExecutorSetMonitorCallbackEX(exe, monitor, None, 1) == 0
    assert lib.MXExecutorForward(exe, 0) == 0
    assert any(vals == [1.0, 4.0, 9.0, 16.0] for _, vals in seen), seen
    lib.MXExecutorFree(exe)
    for hh in (h, out, rid, r2, xa):
        lib.MXNDArrayFree(hh)


_FRONTEND_EXTRAS_MAIN = r"""
#include <cstdio>
#include <cmath>
#include "mxnet_tpu_cpp/MxNetCpp.h"

using namespace mxnet_tpu_cpp;

static int g_stat_calls = 0;
static float CountingStat(const std::vector<float>& v) {
  ++g_stat_calls;
  return Monitor::MeanAbs(v);
}

int main() {
  // Shape value type
  Shape s{2, 3, 4};
  if (s.Size() != 24 || s.ndim() != 3) { std::printf("FAIL shape\n"); return 1; }
  NDArray from_shape(s);          // Shape converts into the NDArray API
  if (from_shape.Size() != 24) { std::printf("FAIL shape ctor\n"); return 1; }

  // initializers: name dispatch + xavier scaling
  NDArray w({64, 32}), b({64}), g({64});
  Xavier xav(Xavier::gaussian, Xavier::avg, 3.0f);
  xav("fc_weight", &w);
  xav("fc_bias", &b);
  xav("bn_gamma", &g);
  auto wv = w.CopyTo(); auto bv = b.CopyTo(); auto gv = g.CopyTo();
  double wsum = 0, wabs = 0;
  for (float v : wv) { wsum += v; wabs += std::fabs(v); }
  bool bias_zero = true, gamma_one = true;
  for (float v : bv) if (v != 0.0f) bias_zero = false;
  for (float v : gv) if (v != 1.0f) gamma_one = false;
  std::printf("init bias_zero=%d gamma_one=%d wabs_mean=%.4f\n",
              bias_zero ? 1 : 0, gamma_one ? 1 : 0, wabs / wv.size());
  // xavier std = sqrt(3/48) ~ 0.25 -> mean|x| ~ 0.2; loose sanity band
  if (!(wabs / wv.size() > 0.05 && wabs / wv.size() < 0.5)) {
    std::printf("FAIL xavier scale\n"); return 1;
  }

  // lr schedules
  FactorScheduler fs(10, 0.5f, 1e-6f, 1.0f);
  MultiFactorScheduler ms({5, 8}, 0.1f, 1.0f);
  std::printf("lr fs@25=%.3f ms@9=%.3f\n", fs.GetLR(25), ms.GetLR(9));
  if (std::fabs(fs.GetLR(25) - 0.25f) > 1e-6) { std::printf("FAIL fs\n"); return 1; }
  if (std::fabs(ms.GetLR(9) - 0.01f) > 1e-7) { std::printf("FAIL ms\n"); return 1; }

  // metrics
  NDArray preds({2, 3}), labels({2});
  preds.CopyFrom({0.1f, 0.7f, 0.2f, 0.6f, 0.3f, 0.1f});
  labels.CopyFrom({1.0f, 2.0f});
  Accuracy acc;
  acc.Update(labels, preds);
  RMSE rmse;
  NDArray a({3}), p({3});
  a.CopyFrom({1, 2, 3}); p.CopyFrom({1, 2, 5});
  rmse.Update(a, p);
  std::printf("acc=%.2f rmse=%.4f\n", acc.Get(), rmse.Get());
  if (std::fabs(acc.Get() - 0.5f) > 1e-6) { std::printf("FAIL acc\n"); return 1; }

  // monitor on an executor forward
  Symbol x = Symbol::Variable("x");
  Symbol y = Symbol::Atomic("square", {}, "sq");
  y.Compose({{"x", &x}});  // square's input slot is named x
  NDArray xv({4});
  Executor exe(y, {"x"}, {&xv});      // example fixes the shape only
  NDArray arg = exe.Arg("x");
  arg.CopyFrom({1, -2, 3, -4});       // bound value set in place
  Monitor mon;
  mon.Install(exe.handle(), true);
  exe.Forward(false);
  auto stats = mon.toc();
  bool saw = false;
  for (auto& kv : stats)
    if (kv.second > 7.49f && kv.second < 7.51f) saw = true;  // mean|sq| = 7.5
  std::printf("monitor stats=%zu saw_sq=%d\n", stats.size(), saw ? 1 : 0);
  if (!saw) { std::printf("FAIL monitor\n"); return 1; }
  {
    Monitor scoped(&CountingStat);       // uninstalls on destruction
    scoped.Install(exe.handle(), true);
    exe.Forward(false);                  // proves the callback is wired
    if (g_stat_calls == 0) { std::printf("FAIL scoped wiring\n"); return 1; }
  }
  int calls_at_destroy = g_stat_calls;
  exe.Forward(false);                    // must not call into dead state
  if (g_stat_calls != calls_at_destroy) {
    std::printf("FAIL uninstall no-op: callback fired after destroy\n");
    return 1;
  }
  std::printf("post-destroy forward ok\n");

  std::printf("EXTRAS OK\n");
  return 0;
}
"""


def test_cpp_frontend_extras(tmp_path, c_api_lib):
    """New cpp-package mirrors: Shape, initializers (name dispatch +
    Xavier scaling), LR schedulers, metrics, executor Monitor through
    the ABI monitor callback."""
    src = tmp_path / "extras.cc"
    src.write_text(_FRONTEND_EXTRAS_MAIN)
    exe = _compile(tmp_path, str(src), c_api_lib, "extras")
    r = subprocess.run([exe], env=_child_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "EXTRAS OK" in r.stdout, r.stdout


_KVSTORE_CPP_MAIN = r"""
#include <cstdio>
#include <cmath>
#include "mxnet_tpu_cpp/MxNetCpp.h"

using namespace mxnet_tpu_cpp;

static int g_upd_calls = 0;

static void SgdHalf(const char* key, NDArrayHandle recv,
                    NDArrayHandle local, void* state) {
  ++g_upd_calls;
  NDArray r = NDArray::Borrow(recv), l = NDArray::Borrow(local);
  auto rv = r.CopyTo(); auto lv = l.CopyTo();
  for (size_t i = 0; i < lv.size(); ++i) lv[i] -= 0.5f * rv[i];
  l.CopyFrom(lv);
  (void)key; (void)state;
}

int main() {
  if (!KVStore::IsWorkerNode() || KVStore::IsServerNode()) {
    std::printf("FAIL roles\n"); return 1;
  }
  KVStore kv("local");
  NDArray w({4}), g({4}), out({4});
  w.CopyFrom({1, 1, 1, 1});
  g.CopyFrom({2, 2, 2, 2});
  kv.Init({"w"}, {&w});
  kv.SetUpdater(&SgdHalf);
  kv.Push({"w"}, {&g});
  kv.Pull({"w"}, {&out});
  auto ov = out.CopyTo();
  int dead = kv.NumDeadNode(0, 5);
  std::printf("pull=%.1f upd_calls=%d dead=%d\n", ov[0], g_upd_calls,
              dead);
  if (std::fabs(ov[0] - 0.0f) > 1e-6 || g_upd_calls != 1 || dead != 0) {
    std::printf("FAIL updater\n"); return 1;
  }
  kv.SetUpdater(nullptr);               // clears; store-write semantics
  kv.Push({"w"}, {&g});
  kv.Pull({"w"}, {&out});
  if (std::fabs(out.CopyTo()[0] - 2.0f) > 1e-6) {
    std::printf("FAIL updater clear\n"); return 1;
  }
  kv.SetGradientCompression({{"type", "2bit"}, {"threshold", "0.5"}});
  kv.Barrier();
  // pushpull on a second, optimizer-driven store
  KVStore kv2("local");
  NDArray w2({4}), g2({4}), o2({4});
  w2.CopyFrom({1, 1, 1, 1});
  g2.CopyFrom({4, 4, 4, 4});
  kv2.Init({"p"}, {&w2});
  kv2.SetOptimizer("sgd", {{"learning_rate", "0.25"}});
  kv2.PushPull({"p"}, {&g2}, {&o2});
  auto o2v = o2.CopyTo();
  std::printf("pushpull=%.2f\n", o2v[0]);  // 1 - 0.25*4 = 0
  if (std::fabs(o2v[0]) > 1e-5) { std::printf("FAIL pushpull\n"); return 1; }
  std::printf("KV OK\n");
  return 0;
}
"""


def test_cpp_kvstore_full_surface(tmp_path, c_api_lib):
    """C++ KVStore mirror: roles, typed updater callback, gradient
    compression, barrier, optimizer-driven pushpull, dead-node query."""
    src = tmp_path / "kvcpp.cc"
    src.write_text(_KVSTORE_CPP_MAIN)
    exe = _compile(tmp_path, str(src), c_api_lib, "kvcpp")
    r = subprocess.run([exe], env=_child_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "KV OK" in r.stdout, r.stdout


_OPERATOR_CPP_MAIN = r"""
#include <cstdio>
#include <cmath>
#include "mxnet_tpu_cpp/MxNetCpp.h"

using namespace mxnet_tpu_cpp;

int main() {
  // the reference mxnet-cpp idiom: fluent Operator chaining
  Symbol data = Symbol::Variable("data");
  uint32_t hidden = 8;                 // unsigned params must compile
  Symbol fc1 = Operator("FullyConnected")
                   .SetParam("num_hidden", hidden)
                   .SetInput("data", data)
                   .CreateSymbol("fc1");
  Symbol act = Operator("Activation")
                   .SetParam("act_type", "tanh")(fc1)
                   .CreateSymbol("act");
  Symbol fc2 = Operator("FullyConnected")
                   .SetParam("num_hidden", 3)
                   .SetInput("data", act)
                   .CreateSymbol("fc2");

  uint32_t n_args = 0;
  const char** names = nullptr;
  Check(MXSymbolListArguments(fc2.handle(), &n_args, &names));
  std::printf("args=%u\n", n_args);  // data + 2x(weight,bias)
  if (n_args != 5) { std::printf("FAIL args\n"); return 1; }

  NDArray x({4, 16});
  Executor exe(fc2, {"data"}, {&x});
  Xavier xav;
  // initialize every bound argument by name through the executor
  const char* wnames[] = {"fc1_weight", "fc1_bias", "fc2_weight",
                          "fc2_bias"};
  for (const char* n : wnames) {
    NDArray a = exe.Arg(n);
    xav(n, &a);
  }
  NDArray din = exe.Arg("data");
  std::vector<float> xv(64);
  for (int i = 0; i < 64; ++i) xv[i] = (i % 7 - 3) / 3.0f;
  din.CopyFrom(xv);
  exe.Forward(false);
  auto outs = exe.Outputs();
  auto ov = outs[0].CopyTo();
  bool finite = true;
  for (float v : ov) if (!std::isfinite(v)) finite = false;
  std::printf("out=%zu finite=%d\n", ov.size(), finite ? 1 : 0);
  if (ov.size() != 12 || !finite) { std::printf("FAIL fwd\n"); return 1; }
  // positional wiring of a binary op: both inputs must survive
  Symbol a = Symbol::Variable("a"), b = Symbol::Variable("b");
  Symbol sum = Operator("elemwise_add")(a)(b).CreateSymbol("sum");
  NDArray av({3}), bv({3});
  Executor exe2(sum, {"a", "b"}, {&av, &bv});
  NDArray aa = exe2.Arg("a"), bb = exe2.Arg("b");
  aa.CopyFrom({1, 2, 3});
  bb.CopyFrom({10, 20, 30});
  exe2.Forward(false);
  auto sv = exe2.Outputs()[0].CopyTo();
  std::printf("sum=%.0f %.0f %.0f\n", sv[0], sv[1], sv[2]);
  if (sv[0] != 11 || sv[1] != 22 || sv[2] != 33) {
    std::printf("FAIL positional\n"); return 1;
  }
  std::printf("OPERATOR OK\n");
  return 0;
}
"""


def test_cpp_operator_chaining(tmp_path, c_api_lib):
    """The mxnet-cpp Operator idiom: fluent SetParam/SetInput chaining
    building a 2-layer MLP, bound and run through the executor with
    name-dispatched initialization."""
    src = tmp_path / "opcpp.cc"
    src.write_text(_OPERATOR_CPP_MAIN)
    exe = _compile(tmp_path, str(src), c_api_lib, "opcpp")
    r = subprocess.run([exe], env=_child_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OPERATOR OK" in r.stdout, r.stdout


def test_cpp_lenet_operator_example(tmp_path, c_api_lib):
    """examples/cpp/train_lenet_operator.cc: a conv net composed with
    the Operator idiom trains to >0.9 accuracy using the full frontend
    mirror set (Xavier, FactorScheduler, Accuracy, executor grads)."""
    src = os.path.join(REPO, "examples", "cpp", "train_lenet_operator.cc")
    exe = _compile(tmp_path, src, c_api_lib, "lenet_op")
    r = subprocess.run([exe], env=_child_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LENET OK" in r.stdout, r.stdout


def test_c_api_infer_shape_partial_and_iter_index(tmp_path, c_api_lib):
    """Remaining batch-5 corners: InferShapePartial leaves unknowable
    shapes empty with complete=0; DataIterGetIndex errors cleanly on an
    iterator without sample indices."""
    import ctypes
    import mxnet_tpu as mx
    lib = ctypes.CDLL(c_api_lib)
    lib.MXGetLastError.restype = ctypes.c_char_p

    # two-input graph, only one shape given -> partial succeeds,
    # full infer reports incomplete rather than erroring
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    s = mx.sym.elemwise_add(a, mx.sym.square(b), name="s")
    sym = ctypes.c_void_p(id(s))
    keys = (ctypes.c_char_p * 1)(b"a")
    ind_ptr = (ctypes.c_uint32 * 2)(0, 2)
    shape_data = (ctypes.c_uint32 * 2)(2, 3)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u32pp = ctypes.POINTER(u32p)
    in_sz = ctypes.c_uint32()
    in_nd = u32p()
    in_dat = u32pp()
    out_sz = ctypes.c_uint32()
    out_nd = u32p()
    out_dat = u32pp()
    aux_sz = ctypes.c_uint32()
    aux_nd = u32p()
    aux_dat = u32pp()
    comp = ctypes.c_int(-1)
    assert lib.MXSymbolInferShapePartial(
        sym, 1, keys, ind_ptr, shape_data, ctypes.byref(in_sz),
        ctypes.byref(in_nd), ctypes.byref(in_dat), ctypes.byref(out_sz),
        ctypes.byref(out_nd), ctypes.byref(out_dat),
        ctypes.byref(aux_sz), ctypes.byref(aux_nd),
        ctypes.byref(aux_dat), ctypes.byref(comp)) == 0
    assert comp.value == 0               # b unknowable
    # the known input keeps its shape; b's entry is empty (ndim 0)
    ndims = [in_nd[i] for i in range(in_sz.value)]
    assert sorted(ndims) == [0, 2]

    # MNISTIter has no per-sample index buffer -> clean error
    import struct
    img_path = str(tmp_path / "im.idx")
    lbl_path = str(tmp_path / "lb.idx")
    import numpy as np
    with open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, 4, 4, 4))
        f.write(np.zeros((4, 4, 4), np.uint8).tobytes())
    with open(lbl_path, "wb") as f:
        f.write(struct.pack(">II", 2049, 4))
        f.write(np.zeros((4,), np.uint8).tobytes())
    it = ctypes.c_void_p()
    ik = (ctypes.c_char_p * 3)(b"image", b"label", b"batch_size")
    iv = (ctypes.c_char_p * 3)(img_path.encode(), lbl_path.encode(), b"2")
    assert lib.MXDataIterCreateIter(b"MNISTIter", 3, ik, iv,
                                    ctypes.byref(it)) == 0
    has = ctypes.c_int()
    assert lib.MXDataIterNext(it, ctypes.byref(has)) == 0 and has.value
    idx = ctypes.POINTER(ctypes.c_uint64)()
    n = ctypes.c_uint64()
    rc = lib.MXDataIterGetIndex(it, ctypes.byref(idx), ctypes.byref(n))
    if rc == 0:
        assert n.value > 0               # indices provided
    else:
        assert b"indices" in lib.MXGetLastError()
    lib.MXDataIterFree(it)


def test_c_api_kvstore_run_server(tmp_path, c_api_lib):
    """MXKVStoreRunServer: a server-role process driven purely through
    the C ABI serves a dist_sync worker (init/push/pull round
    trip), proving the blocking server loop entry point.
    (dist_sync, not dist_tpu_sync: the latter no longer dials a PS at
    all — its hot path is the in-program collective.)"""
    import socket
    import time as _time
    import numpy as np

    # port 0: the server binds an ephemeral port and announces it on
    # stdout (no bind-then-close TOCTOU race)
    code = (
        "import ctypes, os\n"
        "os.environ.update(MXNET_TPU_ROLE='server',\n"
        "                  MXNET_TPU_PS_PORT='0',\n"
        "                  MXNET_TPU_NUM_WORKERS='1',\n"
        "                  MXNET_TPU_PS_MODE='sync')\n"
        "lib = ctypes.CDLL(%r)\n"
        "kv = ctypes.c_void_p()\n"
        "assert lib.MXKVStoreCreate(b'local', ctypes.byref(kv)) == 0\n"
        "lib.MXKVStoreRunServer(kv, None, None)\n" % (c_api_lib,))
    proc = subprocess.Popen([sys.executable, "-u", "-c", code],
                            env=_child_env(), stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    try:
        line = proc.stdout.readline().decode()  # 'listening on <port>'
        assert "listening on" in line, (
            line + proc.stderr.read().decode()
            if proc.poll() is not None else line)
        port = int(line.split("listening on")[1].split()[0])
        with socket.create_connection(("127.0.0.1", port), timeout=30):
            pass

        import mxnet_tpu as mx
        env = {"MXNET_TPU_PS_URI": "127.0.0.1",
               "MXNET_TPU_PS_PORT": str(port),
               "MXNET_TPU_RANK": "0", "MXNET_TPU_NUM_WORKERS": "1"}
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            kv = mx.kv.create("dist_sync")
            kv.init("w", mx.nd.zeros((4,)))
            kv.push("w", mx.nd.array(np.full((4,), 5.0, np.float32)))
            out = mx.nd.zeros((4,))
            kv.pull("w", out=out)
            np.testing.assert_allclose(out.asnumpy(), np.full((4,), 5.0))
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    finally:
        proc.terminate()
        proc.wait(timeout=30)
