// C predict ABI for mxnet_tpu.
//
// Capability analog of the reference's standalone inference ABI
// (include/mxnet/c_predict_api.h, src/c_api/c_predict_api.cc): a flat C
// surface a serving process or foreign language binding links against.
//
// TPU-native design: the compute path is XLA, which is only reachable
// through the Python-hosted JAX runtime — so this library EMBEDS
// CPython (Py_Initialize + GIL discipline) and drives the thin
// marshalling helper mxnet_tpu/serving.py. The C side stays a stable
// ~9-function ABI; everything model/shape/dtype-shaped lives behind it.
// cpp-package/include/mxnet_tpu_cpp/predictor.hpp wraps this in C++.
//
// Build: see src/native/Makefile (g++ -shared, python3-config flags).

#include <Python.h>

#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

#define MXTPU_API extern "C" __attribute__((visibility("default")))

typedef void* PredictorHandle;

namespace {

std::mutex g_err_mutex;
std::string g_last_error;

void set_last_error(const std::string& msg) {
  std::lock_guard<std::mutex> lock(g_err_mutex);
  g_last_error = msg;
}

// Record the active python exception into the error slot.
void capture_py_error() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  std::string msg = "python error";
  if (value != nullptr) {
    PyObject* s = PyObject_Str(value);
    if (s != nullptr) {
      const char* c = PyUnicode_AsUTF8(s);
      if (c != nullptr) msg = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
  set_last_error(msg);
}

struct Predictor {
  PyObject* obj;  // mxnet_tpu.serving.Predictor instance
};

// Ensure the interpreter is up; returns a GIL guard state.
bool ensure_python(PyGILState_STATE* state) {
  if (!Py_IsInitialized()) {
    // Embedded start: inherit env (JAX_PLATFORMS etc.)
    Py_InitializeEx(0);
    if (!Py_IsInitialized()) {
      set_last_error("failed to initialize embedded python");
      return false;
    }
    // Release the GIL acquired by initialization so PyGILState works
    // from any caller thread.
    PyEval_SaveThread();
  }
  *state = PyGILState_Ensure();
  return true;
}

}  // namespace

MXTPU_API const char* MXGetLastError() {
  std::lock_guard<std::mutex> lock(g_err_mutex);
  return g_last_error.c_str();
}

// Create a predictor from a symbol json and an mx.nd.save params blob.
// input_shape_indptr/input_shape_data follow the reference's CSR-style
// shape packing (c_predict_api.h MXPredCreate).
MXTPU_API int MXPredCreate(const char* symbol_json_str,
                           const void* param_bytes, int param_size,
                           int dev_type, int dev_id,
                           uint32_t num_input_nodes,
                           const char** input_keys,
                           const uint32_t* input_shape_indptr,
                           const uint32_t* input_shape_data,
                           PredictorHandle* out) {
  PyGILState_STATE gil;
  if (!ensure_python(&gil)) return -1;
  int ret = -1;
  PyObject* mod = nullptr;
  PyObject* cls = nullptr;
  PyObject* shapes = nullptr;
  PyObject* args = nullptr;
  PyObject* obj = nullptr;
  do {
    mod = PyImport_ImportModule("mxnet_tpu.serving");
    if (mod == nullptr) { capture_py_error(); break; }
    cls = PyObject_GetAttrString(mod, "Predictor");
    if (cls == nullptr) { capture_py_error(); break; }
    shapes = PyDict_New();
    for (uint32_t i = 0; i < num_input_nodes; ++i) {
      uint32_t lo = input_shape_indptr[i], hi = input_shape_indptr[i + 1];
      PyObject* shp = PyTuple_New(hi - lo);
      for (uint32_t j = lo; j < hi; ++j) {
        PyTuple_SET_ITEM(shp, j - lo,
                         PyLong_FromUnsignedLong(input_shape_data[j]));
      }
      PyDict_SetItemString(shapes, input_keys[i], shp);
      Py_DECREF(shp);
    }
    PyObject* params = PyBytes_FromStringAndSize(
        static_cast<const char*>(param_bytes), param_size);
    args = Py_BuildValue("(sNiiO)", symbol_json_str, params, dev_type,
                         dev_id, shapes);
    if (args == nullptr) { capture_py_error(); break; }
    obj = PyObject_CallObject(cls, args);
    if (obj == nullptr) { capture_py_error(); break; }
    Predictor* p = new Predictor{obj};
    obj = nullptr;  // ownership moved
    *out = p;
    ret = 0;
  } while (false);
  Py_XDECREF(obj);
  Py_XDECREF(args);
  Py_XDECREF(shapes);
  Py_XDECREF(cls);
  Py_XDECREF(mod);
  PyGILState_Release(gil);
  return ret;
}

MXTPU_API int MXPredSetInput(PredictorHandle handle, const char* key,
                             const float* data, uint32_t size) {
  PyGILState_STATE gil;
  if (!ensure_python(&gil)) return -1;
  Predictor* p = static_cast<Predictor*>(handle);
  PyObject* bytes = PyBytes_FromStringAndSize(
      reinterpret_cast<const char*>(data), size * sizeof(float));
  PyObject* r = PyObject_CallMethod(p->obj, "set_input", "sN", key, bytes);
  int ret = 0;
  if (r == nullptr) { capture_py_error(); ret = -1; }
  Py_XDECREF(r);
  PyGILState_Release(gil);
  return ret;
}

MXTPU_API int MXPredForward(PredictorHandle handle) {
  PyGILState_STATE gil;
  if (!ensure_python(&gil)) return -1;
  Predictor* p = static_cast<Predictor*>(handle);
  PyObject* r = PyObject_CallMethod(p->obj, "forward", nullptr);
  int ret = 0;
  if (r == nullptr) { capture_py_error(); ret = -1; }
  Py_XDECREF(r);
  PyGILState_Release(gil);
  return ret;
}

MXTPU_API int MXPredGetOutputShape(PredictorHandle handle, uint32_t index,
                                   uint32_t* shape_data,
                                   uint32_t* shape_ndim) {
  PyGILState_STATE gil;
  if (!ensure_python(&gil)) return -1;
  Predictor* p = static_cast<Predictor*>(handle);
  PyObject* r = PyObject_CallMethod(p->obj, "get_output_shape", "I", index);
  int ret = -1;
  if (r != nullptr && PyTuple_Check(r)) {
    Py_ssize_t n = PyTuple_Size(r);
    *shape_ndim = static_cast<uint32_t>(n);
    if (shape_data != nullptr) {
      for (Py_ssize_t i = 0; i < n; ++i) {
        shape_data[i] = static_cast<uint32_t>(
            PyLong_AsUnsignedLong(PyTuple_GetItem(r, i)));
      }
    }
    ret = 0;
  } else {
    capture_py_error();
  }
  Py_XDECREF(r);
  PyGILState_Release(gil);
  return ret;
}

MXTPU_API int MXPredGetOutput(PredictorHandle handle, uint32_t index,
                              float* data, uint32_t size) {
  PyGILState_STATE gil;
  if (!ensure_python(&gil)) return -1;
  Predictor* p = static_cast<Predictor*>(handle);
  PyObject* r = PyObject_CallMethod(p->obj, "get_output", "I", index);
  int ret = -1;
  if (r != nullptr && PyBytes_Check(r)) {
    Py_ssize_t n = PyBytes_Size(r);
    if (static_cast<uint32_t>(n) != size * sizeof(float)) {
      set_last_error("output size mismatch");
    } else {
      std::memcpy(data, PyBytes_AsString(r), n);
      ret = 0;
    }
  } else {
    capture_py_error();
  }
  Py_XDECREF(r);
  PyGILState_Release(gil);
  return ret;
}

MXTPU_API int MXPredFree(PredictorHandle handle) {
  PyGILState_STATE gil;
  if (!ensure_python(&gil)) return -1;
  Predictor* p = static_cast<Predictor*>(handle);
  Py_XDECREF(p->obj);
  delete p;
  PyGILState_Release(gil);
  return 0;
}
