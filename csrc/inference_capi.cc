// C inference API (reference paddle/fluid/inference/capi/c_api.cc:
// PD_NewPredictor / PD_PredictorRun / PD_DeletePredictor over PD_Tensor).
//
// TPU redesign: the reference's C API fronts a C++ AnalysisPredictor; here
// the predictor IS the XLA runtime reached through an embedded CPython
// (the StableHLO artifact compiles/executes inside jax). The C surface
// matches the reference's shape: opaque predictor handle, run with raw
// float32 buffers + shapes, outputs malloc'd for the caller,
// PD_GetLastError for diagnostics. Single-threaded contract (one GIL
// owner), float32 tensors; build: `make libpd_infer_capi.so`.
#include <Python.h>

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <string>

static std::string g_err;

static void set_err_from_python() {
  PyObject *t = nullptr, *v = nullptr, *tb = nullptr;
  PyErr_Fetch(&t, &v, &tb);
  PyObject* s = v ? PyObject_Str(v) : nullptr;
  const char* c = s ? PyUnicode_AsUTF8(s) : nullptr;
  g_err = c ? c : "unknown python error";
  Py_XDECREF(s);
  Py_XDECREF(t);
  Py_XDECREF(v);
  Py_XDECREF(tb);
}

extern "C" {

struct PD_Predictor {
  PyObject* pred;
};

const char* PD_GetLastError() { return g_err.c_str(); }

// the embedded interpreter reads JAX_PLATFORMS from the environment like
// any other python process
static void ensure_python() {
  if (!Py_IsInitialized()) Py_InitializeEx(0);
}

PD_Predictor* PD_NewPredictor(const char* model_prefix) {
  ensure_python();
  PyObject* mod = PyImport_ImportModule("paddle_tpu.inference");
  if (!mod) {
    set_err_from_python();
    return nullptr;
  }
  PyObject* cfg_cls = PyObject_GetAttrString(mod, "Config");
  PyObject* cfg =
      cfg_cls ? PyObject_CallFunction(cfg_cls, "s", model_prefix) : nullptr;
  PyObject* mk =
      cfg ? PyObject_GetAttrString(mod, "create_predictor") : nullptr;
  PyObject* pred = mk ? PyObject_CallFunctionObjArgs(mk, cfg, nullptr)
                      : nullptr;
  Py_XDECREF(mk);
  Py_XDECREF(cfg);
  Py_XDECREF(cfg_cls);
  Py_DECREF(mod);
  if (!pred) {
    set_err_from_python();
    return nullptr;
  }
  PD_Predictor* h = new PD_Predictor();
  h->pred = pred;
  return h;
}

// Run with one float32 input; outputs the first result tensor.
// out_data is malloc'd (caller frees via PD_FreeBuffer); out_shape must
// hold up to 8 dims; returns 0 on success.
int PD_PredictorRun(PD_Predictor* h, const float* input,
                    const int64_t* shape, int ndim, float** out_data,
                    int64_t* out_shape, int* out_ndim) {
  if (!h || !h->pred) {
    g_err = "null predictor";
    return 1;
  }
  int64_t total = 1;
  for (int i = 0; i < ndim; ++i) total *= shape[i];

  PyObject* np = PyImport_ImportModule("numpy");
  if (!np) {
    set_err_from_python();
    return 2;
  }
  PyObject* mv = PyMemoryView_FromMemory(
      const_cast<char*>(reinterpret_cast<const char*>(input)),
      total * static_cast<int64_t>(sizeof(float)), PyBUF_READ);
  PyObject* flat =
      mv ? PyObject_CallMethod(np, "frombuffer", "Os", mv, "float32")
         : nullptr;
  PyObject* pyshape = PyTuple_New(ndim);
  for (int i = 0; i < ndim; ++i)
    PyTuple_SetItem(pyshape, i, PyLong_FromLongLong(shape[i]));
  PyObject* arr =
      flat ? PyObject_CallMethod(flat, "reshape", "O", pyshape) : nullptr;
  PyObject* out_list =
      arr ? PyObject_CallMethod(h->pred, "run", "[O]", arr) : nullptr;
  int rc = 0;
  if (!out_list || !PyList_Check(out_list) || PyList_Size(out_list) < 1) {
    set_err_from_python();
    rc = 3;
  } else {
    PyObject* out0 = PyList_GetItem(out_list, 0);  // borrowed
    PyObject* cont =
        PyObject_CallMethod(np, "ascontiguousarray", "Os", out0, "float32");
    PyObject* bytes =
        cont ? PyObject_CallMethod(cont, "tobytes", nullptr) : nullptr;
    PyObject* oshape =
        cont ? PyObject_GetAttrString(cont, "shape") : nullptr;
    if (!bytes || !oshape) {
      set_err_from_python();
      rc = 4;
    } else if (PyTuple_Size(oshape) > 8) {
      g_err = "output rank > 8 unsupported by the C API";
      rc = 5;
    } else {
      char* buf;
      Py_ssize_t blen;
      PyBytes_AsStringAndSize(bytes, &buf, &blen);
      *out_data = static_cast<float*>(malloc(blen));
      memcpy(*out_data, buf, blen);
      *out_ndim = static_cast<int>(PyTuple_Size(oshape));
      for (int i = 0; i < *out_ndim; ++i)
        out_shape[i] = PyLong_AsLongLong(PyTuple_GetItem(oshape, i));
    }
    Py_XDECREF(oshape);
    Py_XDECREF(bytes);
    Py_XDECREF(cont);
  }
  Py_XDECREF(out_list);
  Py_XDECREF(arr);
  Py_XDECREF(pyshape);
  Py_XDECREF(flat);
  Py_XDECREF(mv);
  Py_DECREF(np);
  return rc;
}

void PD_FreeBuffer(void* p) { free(p); }

void PD_DeletePredictor(PD_Predictor* h) {
  if (h) {
    Py_XDECREF(h->pred);
    delete h;
  }
}

}  // extern "C"
