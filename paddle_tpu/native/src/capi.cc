// C inference API.
//
// Native equivalent of the reference's pure-C predictor wrapper
// (/root/reference/paddle/fluid/inference/capi/pd_predictor.cc,
// pd_config.cc, paddle_c_api.h): lets C/C++/Go applications run models
// exported with jit.save without linking Python code themselves. The
// reference wraps its C++ AnalysisPredictor; the TPU build's predictor is
// the XLA-compiled TranslatedLayer behind paddle_tpu.inference, so this
// library embeds CPython (libpython) and drives that predictor through a
// small helper module. Build via paddle_tpu.native.load_library("capi",
// python-config flags) or: g++ -shared -fPIC capi.cc $(python3-config
// --includes --embed --libs).
//
// Threading: every entry point takes the GIL (PyGILState_Ensure), so the
// API is safe to call from any single thread at a time.

#include <Python.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

namespace {

const char kHelperSrc[] = R"PY(
import os

import numpy as np

def _new_predictor(prefix):
    from paddle_tpu import inference
    cfg = inference.Config(prefix)
    return inference.Predictor(cfg)

def _set_input(feeds, name, buf, shape, dtype):
    feeds[name] = np.frombuffer(buf, dtype=dtype).reshape(shape).copy()

def _run(pred, feeds):
    names = pred.get_input_names()
    arrays = [feeds[n] for n in names]
    outs = pred.run(arrays)
    res = []
    for a in outs:
        a = np.ascontiguousarray(np.asarray(a, dtype=np.float32))
        res.append((a.tobytes(), list(a.shape)))
    return res

def _new_trainer(dirpath):
    # C++ train-demo parity (reference fluid/train/demo/demo_trainer.cc):
    # load the (main, startup) program pair, run startup once. Each
    # trainer owns a private Scope, so two trainers never clobber each
    # other's parameters.
    import paddle_tpu.static as static
    main = static.load_program(os.path.join(dirpath, "main_program"))
    startup = static.load_program(os.path.join(dirpath, "startup_program"))
    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        exe.run(startup)
    return (exe, main, scope)

def _train_run(tr, feeds, fetch_names):
    import paddle_tpu.static as static
    exe, main, scope = tr
    with static.scope_guard(scope):
        outs = exe.run(main, feed=feeds, fetch_list=list(fetch_names))
    res = []
    for a in outs:
        a = np.ascontiguousarray(np.asarray(a, dtype=np.float32))
        res.append((a.tobytes(), list(a.shape)))
    return res

def _train_save(tr, dirname):
    exe, main, scope = tr
    import paddle_tpu.static as static
    with static.scope_guard(scope):
        static.save_persistables(exe, dirname, main)
)PY";

struct Output {
  PyObject* bytes = nullptr;  // owned ref; data pointer stays valid
  std::vector<int64_t> shape;
};

std::string g_last_error;
PyObject* g_helper = nullptr;  // module dict
bool g_we_initialized = false;

void set_error_from_python() {
  PyObject *type, *value, *tb;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  if (value != nullptr) {
    PyObject* s = PyObject_Str(value);
    if (s != nullptr) {
      g_last_error = PyUnicode_AsUTF8(s) ? PyUnicode_AsUTF8(s) : "unknown";
      Py_DECREF(s);
    }
  } else {
    g_last_error = "unknown python error";
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

bool ensure_helper() {
  if (g_helper != nullptr) return true;
  bool initialized_here = false;
  if (!Py_IsInitialized()) {
    Py_InitializeEx(0);
    g_we_initialized = true;
    initialized_here = true;
  }
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* globals = PyDict_New();
  PyDict_SetItemString(globals, "__builtins__", PyEval_GetBuiltins());
  PyObject* r = PyRun_String(kHelperSrc, Py_file_input, globals, globals);
  bool ok = r != nullptr;
  if (!ok) {
    set_error_from_python();
    Py_DECREF(globals);
  } else {
    Py_DECREF(r);
    g_helper = globals;
  }
  PyGILState_Release(gil);
  if (initialized_here) {
    // Py_InitializeEx leaves this thread holding the GIL; release it so
    // other threads' PyGILState_Ensure can proceed (the header promises
    // any-single-thread-at-a-time safety).
    PyEval_SaveThread();
  }
  return ok;
}

PyObject* helper_call(const char* fn, PyObject* args) {
  PyObject* f = PyDict_GetItemString(g_helper, fn);  // borrowed
  if (f == nullptr) {
    g_last_error = std::string("helper missing: ") + fn;
    return nullptr;
  }
  PyObject* out = PyObject_CallObject(f, args);
  if (out == nullptr) set_error_from_python();
  return out;
}

// Shared feed staging (predictor + trainer): copy a raw buffer into the
// feeds dict as an ndarray. GIL taken by the caller-facing wrappers.
int stage_input(PyObject* feeds, const char* name, const void* data,
                int64_t elem_size, const char* dtype, const int64_t* shape,
                int ndim) {
  PyGILState_STATE gil = PyGILState_Ensure();
  int64_t n = 1;
  PyObject* shp = PyList_New(ndim);
  for (int i = 0; i < ndim; ++i) {
    n *= shape[i];
    PyList_SetItem(shp, i, PyLong_FromLongLong(shape[i]));
  }
  PyObject* buf = PyBytes_FromStringAndSize(
      static_cast<const char*>(data), n * elem_size);
  PyObject* args = Py_BuildValue("(OsOOs)", feeds, name, buf, shp, dtype);
  PyObject* r = helper_call("_set_input", args);
  Py_DECREF(args);
  Py_DECREF(buf);
  Py_DECREF(shp);
  int rc = (r == nullptr) ? -1 : 0;
  Py_XDECREF(r);
  PyGILState_Release(gil);
  return rc;
}

// Shared fetch unpacking: [(bytes, shape), ...] -> outputs. Caller holds
// the GIL and has cleared the previous outputs.
void collect_outputs(PyObject* res, std::vector<Output>* outputs) {
  for (Py_ssize_t i = 0; i < PyList_Size(res); ++i) {
    PyObject* item = PyList_GetItem(res, i);  // (bytes, shape)
    Output o;
    o.bytes = PyTuple_GetItem(item, 0);
    Py_INCREF(o.bytes);
    PyObject* shp = PyTuple_GetItem(item, 1);
    for (Py_ssize_t j = 0; j < PyList_Size(shp); ++j)
      o.shape.push_back(PyLong_AsLongLong(PyList_GetItem(shp, j)));
    outputs->push_back(o);
  }
}

}  // namespace

extern "C" {

struct PD_Predictor {
  PyObject* pred = nullptr;
  PyObject* feeds = nullptr;  // dict name -> ndarray
  std::vector<Output> outputs;
  std::vector<std::string> input_names;
};

// Optional: extend sys.path (e.g. the repo root holding paddle_tpu)
// before the first PD_NewPredictor. Safe to call multiple times.
int PD_Init(const char* extra_sys_path) {
  if (!ensure_helper()) return -1;
  if (extra_sys_path == nullptr || extra_sys_path[0] == '\0') return 0;
  PyGILState_STATE gil = PyGILState_Ensure();
  std::string code = "import sys\nsys.path.insert(0, r'''";
  code += extra_sys_path;
  code += "''')\n";
  PyObject* r = PyRun_String(code.c_str(), Py_file_input, g_helper,
                             g_helper);
  int rc = 0;
  if (r == nullptr) {
    set_error_from_python();
    rc = -1;
  }
  Py_XDECREF(r);
  PyGILState_Release(gil);
  return rc;
}

const char* PD_GetLastError() { return g_last_error.c_str(); }

PD_Predictor* PD_NewPredictor(const char* model_prefix) {
  if (!ensure_helper()) return nullptr;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* args = Py_BuildValue("(s)", model_prefix);
  PyObject* pred = helper_call("_new_predictor", args);
  Py_DECREF(args);
  if (pred == nullptr) {
    PyGILState_Release(gil);
    return nullptr;
  }
  PyObject* names = PyObject_CallMethod(pred, "get_input_names", nullptr);
  if (names == nullptr) {
    set_error_from_python();  // fetches + clears the error indicator
    Py_DECREF(pred);
    PyGILState_Release(gil);
    return nullptr;
  }
  PD_Predictor* p = new PD_Predictor();
  p->pred = pred;
  p->feeds = PyDict_New();
  for (Py_ssize_t i = 0; i < PyList_Size(names); ++i) {
    p->input_names.emplace_back(
        PyUnicode_AsUTF8(PyList_GetItem(names, i)));
  }
  Py_DECREF(names);
  PyGILState_Release(gil);
  return p;
}

int PD_GetInputNum(const PD_Predictor* p) {
  return static_cast<int>(p->input_names.size());
}

const char* PD_GetInputName(const PD_Predictor* p, int i) {
  if (i < 0 || i >= static_cast<int>(p->input_names.size())) return nullptr;
  return p->input_names[i].c_str();
}

static int set_input(PD_Predictor* p, const char* name, const void* data,
                     int64_t elem_size, const char* dtype,
                     const int64_t* shape, int ndim) {
  return stage_input(p->feeds, name, data, elem_size, dtype, shape, ndim);
}

int PD_SetInputFloat(PD_Predictor* p, const char* name, const float* data,
                     const int64_t* shape, int ndim) {
  return set_input(p, name, data, 4, "float32", shape, ndim);
}

int PD_SetInputInt64(PD_Predictor* p, const char* name,
                     const int64_t* data, const int64_t* shape, int ndim) {
  return set_input(p, name, data, 8, "int64", shape, ndim);
}

int PD_SetInputInt32(PD_Predictor* p, const char* name,
                     const int32_t* data, const int64_t* shape, int ndim) {
  return set_input(p, name, data, 4, "int32", shape, ndim);
}

// Runs the model on the staged inputs. Output buffers stay valid until
// the next PD_Run or PD_DeletePredictor.
int PD_Run(PD_Predictor* p) {
  PyGILState_STATE gil = PyGILState_Ensure();
  for (Output& o : p->outputs) Py_XDECREF(o.bytes);
  p->outputs.clear();
  PyObject* args = Py_BuildValue("(OO)", p->pred, p->feeds);
  PyObject* res = helper_call("_run", args);
  Py_DECREF(args);
  if (res == nullptr) {
    PyGILState_Release(gil);
    return -1;
  }
  collect_outputs(res, &p->outputs);
  Py_DECREF(res);
  PyGILState_Release(gil);
  return 0;
}

int PD_GetOutputNum(const PD_Predictor* p) {
  return static_cast<int>(p->outputs.size());
}

int PD_GetOutputFloat(const PD_Predictor* p, int idx, const float** data,
                      const int64_t** shape, int* ndim) {
  if (idx < 0 || idx >= static_cast<int>(p->outputs.size())) return -1;
  const Output& o = p->outputs[idx];
  *data = reinterpret_cast<const float*>(PyBytes_AsString(o.bytes));
  *shape = o.shape.data();
  *ndim = static_cast<int>(o.shape.size());
  return 0;
}

// -- trainer: C++ train-demo parity (demo_trainer.cc) ----------------------

struct PD_Trainer {
  PyObject* tr = nullptr;     // (executor, main_program) tuple
  PyObject* feeds = nullptr;  // dict name -> ndarray
  std::vector<Output> outputs;
};

PD_Trainer* PD_NewTrainer(const char* program_dir) {
  if (!ensure_helper()) return nullptr;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* args = Py_BuildValue("(s)", program_dir);
  PyObject* tr = helper_call("_new_trainer", args);
  Py_DECREF(args);
  if (tr == nullptr) {
    PyGILState_Release(gil);
    return nullptr;
  }
  PD_Trainer* t = new PD_Trainer();
  t->tr = tr;
  t->feeds = PyDict_New();
  PyGILState_Release(gil);
  return t;
}

static int trainer_set_input(PD_Trainer* t, const char* name,
                             const void* data, int64_t elem_size,
                             const char* dtype, const int64_t* shape,
                             int ndim) {
  return stage_input(t->feeds, name, data, elem_size, dtype, shape, ndim);
}

int PD_TrainerSetInputFloat(PD_Trainer* t, const char* name,
                            const float* data, const int64_t* shape,
                            int ndim) {
  return trainer_set_input(t, name, data, 4, "float32", shape, ndim);
}

int PD_TrainerSetInputInt64(PD_Trainer* t, const char* name,
                            const int64_t* data, const int64_t* shape,
                            int ndim) {
  return trainer_set_input(t, name, data, 8, "int64", shape, ndim);
}

// One optimizer step over the staged feed; fetches `fetch_names`
// (e.g. the loss) as float32. Buffers valid until next call/delete.
int PD_TrainerRun(PD_Trainer* t, const char** fetch_names,
                  int num_fetch) {
  PyGILState_STATE gil = PyGILState_Ensure();
  for (Output& o : t->outputs) Py_XDECREF(o.bytes);
  t->outputs.clear();
  PyObject* names = PyList_New(num_fetch);
  for (int i = 0; i < num_fetch; ++i)
    PyList_SetItem(names, i, PyUnicode_FromString(fetch_names[i]));
  PyObject* args = Py_BuildValue("(OOO)", t->tr, t->feeds, names);
  PyObject* res = helper_call("_train_run", args);
  Py_DECREF(args);
  Py_DECREF(names);
  if (res == nullptr) {
    PyGILState_Release(gil);
    return -1;
  }
  collect_outputs(res, &t->outputs);
  Py_DECREF(res);
  PyGILState_Release(gil);
  return 0;
}

int PD_TrainerGetFetchFloat(const PD_Trainer* t, int idx,
                            const float** data, const int64_t** shape,
                            int* ndim) {
  if (idx < 0 || idx >= static_cast<int>(t->outputs.size())) return -1;
  const Output& o = t->outputs[idx];
  *data = reinterpret_cast<const float*>(PyBytes_AsString(o.bytes));
  *shape = o.shape.data();
  *ndim = static_cast<int>(o.shape.size());
  return 0;
}

// Save the trained persistables (params + optimizer slots) to dirname.
int PD_TrainerSave(PD_Trainer* t, const char* dirname) {
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* args = Py_BuildValue("(Os)", t->tr, dirname);
  PyObject* r = helper_call("_train_save", args);
  Py_DECREF(args);
  int rc = (r == nullptr) ? -1 : 0;
  Py_XDECREF(r);
  PyGILState_Release(gil);
  return rc;
}

void PD_DeleteTrainer(PD_Trainer* t) {
  if (t == nullptr) return;
  PyGILState_STATE gil = PyGILState_Ensure();
  for (Output& o : t->outputs) Py_XDECREF(o.bytes);
  Py_XDECREF(t->feeds);
  Py_XDECREF(t->tr);
  PyGILState_Release(gil);
  delete t;
}

void PD_DeletePredictor(PD_Predictor* p) {
  if (p == nullptr) return;
  PyGILState_STATE gil = PyGILState_Ensure();
  for (Output& o : p->outputs) Py_XDECREF(o.bytes);
  Py_XDECREF(p->feeds);
  Py_XDECREF(p->pred);
  PyGILState_Release(gil);
  delete p;
}

}  // extern "C"
