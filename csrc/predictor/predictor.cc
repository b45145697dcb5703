// C++ serving predictor over the PJRT C API.
//
// Reference: paddle/fluid/inference/api/analysis_predictor.h:82 — the native
// AnalysisPredictor loads a serialized program + weights, owns device
// buffers, and exposes zero-copy input/output handles. TPU-native version:
// the "analysis passes" are XLA's job, so this loads the StableHLO bytecode
// exported by paddle_tpu.inference.export_model (<prefix>.mlir), compiles it
// through any PJRT plugin (libtpu / a CPU plugin), uploads the
// weights once (<prefix>.pdweights, traced-argument order), and runs with
// per-call input uploads and preallocated host output copies.
//
// Build: make (produces libpdpredictor.so + predictor_cli).
// C ABI (for ctypes / other languages, capi_exp analog):
//   PdPredictor* pd_predictor_create(const char* prefix, const char* plugin);
//   int  pd_predictor_run(PdPredictor*, const void** input_ptrs,
//                         const int32_t* pjrt_types, const int64_t* all_dims,
//                         const int32_t* ndims, int n_inputs);
//   int  pd_predictor_num_outputs(PdPredictor*);
//   long pd_predictor_output_bytes(PdPredictor*, int i);
//   int  pd_predictor_copy_output(PdPredictor*, int i, void* dst, long size);
//   void pd_predictor_destroy(PdPredictor*);
#include <dlfcn.h>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <memory>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

std::string ReadFile(const std::string& path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) return "";
  return std::string((std::istreambuf_iterator<char>(f)),
                     std::istreambuf_iterator<char>());
}

size_t TypeBytes(int32_t t) {
  switch (t) {
    case PJRT_Buffer_Type_PRED:
    case PJRT_Buffer_Type_S8:
    case PJRT_Buffer_Type_U8:
      return 1;
    case PJRT_Buffer_Type_S16:
    case PJRT_Buffer_Type_U16:
    case PJRT_Buffer_Type_F16:
    case PJRT_Buffer_Type_BF16:
      return 2;
    case PJRT_Buffer_Type_S32:
    case PJRT_Buffer_Type_U32:
    case PJRT_Buffer_Type_F32:
      return 4;
    default:
      return 8;
  }
}

struct Tensor {
  int32_t type = 0;
  std::vector<int64_t> dims;
  std::string data;
  size_t elems() const {
    size_t n = 1;
    for (auto d : dims) n *= static_cast<size_t>(d);
    return n;
  }
};

// Client create_options from env PD_PJRT_OPTIONS="k=v;k=v" (plugin-specific;
// libtpu needs none). All-digit values become int64, everything else a
// string.
struct NamedOptions {
  std::vector<std::string> keys, svals;
  std::vector<int64_t> ivals;
  std::vector<bool> is_int;
  std::vector<PJRT_NamedValue> values;

  void Parse(const char* spec) {
    if (!spec) return;
    std::string s(spec);
    size_t pos = 0;
    while (pos < s.size()) {
      size_t semi = s.find(';', pos);
      if (semi == std::string::npos) semi = s.size();
      std::string kv = s.substr(pos, semi - pos);
      pos = semi + 1;
      size_t eq = kv.find('=');
      if (eq == std::string::npos) continue;
      keys.push_back(kv.substr(0, eq));
      std::string v = kv.substr(eq + 1);
      bool digits = !v.empty() &&
                    v.find_first_not_of("0123456789-") == std::string::npos;
      is_int.push_back(digits);
      svals.push_back(v);
      ivals.push_back(digits ? strtoll(v.c_str(), nullptr, 10) : 0);
    }
    for (size_t i = 0; i < keys.size(); ++i) {
      PJRT_NamedValue nv;
      memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = keys[i].c_str();
      nv.name_size = keys[i].size();
      if (is_int[i]) {
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = ivals[i];
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = svals[i].c_str();
        nv.value_size = svals[i].size();
      }
      values.push_back(nv);
    }
  }
};

}  // namespace

struct PdPredictor {
  void* plugin_handle = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  std::vector<PJRT_Buffer*> weight_bufs;  // resident across calls
  std::vector<Tensor> input_meta;
  std::vector<PJRT_Buffer*> outputs;  // last run's device outputs
  std::string last_error;

  bool Check(PJRT_Error* err, const char* what) {
    if (err == nullptr) return true;
    PJRT_Error_Message_Args m;
    memset(&m, 0, sizeof(m));
    m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
    m.error = err;
    api->PJRT_Error_Message(&m);
    last_error = std::string(what) + ": " +
                 std::string(m.message, m.message_size);
    PJRT_Error_Destroy_Args d;
    memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
    d.error = err;
    api->PJRT_Error_Destroy(&d);
    fprintf(stderr, "[pd_predictor] %s\n", last_error.c_str());
    return false;
  }

  bool Await(PJRT_Event* ev, const char* what) {
    if (ev == nullptr) return true;
    PJRT_Event_Await_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    a.event = ev;
    bool ok = Check(api->PJRT_Event_Await(&a), what);
    PJRT_Event_Destroy_Args d;
    memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    d.event = ev;
    api->PJRT_Event_Destroy(&d);
    return ok;
  }

  PJRT_Buffer* Upload(const void* data, int32_t type,
                      const std::vector<int64_t>& dims) {
    PJRT_Client_BufferFromHostBuffer_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    a.client = client;
    a.data = data;
    a.type = static_cast<PJRT_Buffer_Type>(type);
    a.dims = dims.data();
    a.num_dims = dims.size();
    // the copy completes before we free host memory: simplest safe semantics
    a.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    a.device = device;
    if (!Check(api->PJRT_Client_BufferFromHostBuffer(&a), "upload"))
      return nullptr;
    if (!Await(a.done_with_host_buffer, "upload-wait")) return nullptr;
    return a.buffer;
  }

  bool Load(const std::string& prefix, const std::string& plugin_path) {
    plugin_handle = dlopen(plugin_path.c_str(), RTLD_NOW | RTLD_LOCAL);
    if (!plugin_handle) {
      last_error = std::string("dlopen failed: ") + dlerror();
      fprintf(stderr, "[pd_predictor] %s\n", last_error.c_str());
      return false;
    }
    using GetApiFn = const PJRT_Api* (*)();
    auto get_api =
        reinterpret_cast<GetApiFn>(dlsym(plugin_handle, "GetPjrtApi"));
    if (!get_api) {
      last_error = "plugin has no GetPjrtApi";
      return false;
    }
    api = get_api();

    PJRT_Plugin_Initialize_Args init;
    memset(&init, 0, sizeof(init));
    init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (!Check(api->PJRT_Plugin_Initialize(&init), "plugin-init"))
      return false;

    NamedOptions opts;
    opts.Parse(getenv("PD_PJRT_OPTIONS"));
    PJRT_Client_Create_Args cc;
    memset(&cc, 0, sizeof(cc));
    cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    cc.create_options = opts.values.empty() ? nullptr : opts.values.data();
    cc.num_options = opts.values.size();
    if (!Check(api->PJRT_Client_Create(&cc), "client-create")) return false;
    client = cc.client;

    PJRT_Client_AddressableDevices_Args ad;
    memset(&ad, 0, sizeof(ad));
    ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    ad.client = client;
    if (!Check(api->PJRT_Client_AddressableDevices(&ad), "devices"))
      return false;
    if (ad.num_addressable_devices == 0) {
      last_error = "no addressable devices";
      return false;
    }
    device = ad.addressable_devices[0];

    // compile the exported StableHLO with the exported CompileOptionsProto
    std::string code = ReadFile(prefix + ".mlir");
    std::string copts = ReadFile(prefix + ".copts.pb");
    if (code.empty() || copts.empty()) {
      last_error = "missing " + prefix + ".mlir / .copts.pb artifacts";
      fprintf(stderr, "[pd_predictor] %s\n", last_error.c_str());
      return false;
    }
    PJRT_Program program;
    memset(&program, 0, sizeof(program));
    program.struct_size = PJRT_Program_STRUCT_SIZE;
    program.code = code.data();
    program.code_size = code.size();
    static const char kFormat[] = "mlir";
    program.format = kFormat;
    program.format_size = sizeof(kFormat) - 1;

    PJRT_Client_Compile_Args comp;
    memset(&comp, 0, sizeof(comp));
    comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    comp.client = client;
    comp.program = &program;
    comp.compile_options = copts.data();
    comp.compile_options_size = copts.size();
    if (!Check(api->PJRT_Client_Compile(&comp), "compile")) return false;
    exec = comp.executable;

    // upload weights once; they stay resident (AnalysisPredictor semantics)
    std::string wfile = ReadFile(prefix + ".pdweights");
    if (wfile.size() < 8 || wfile.compare(0, 4, "PDW1") != 0) {
      last_error = "bad weights file " + prefix + ".pdweights";
      return false;
    }
    const char* p = wfile.data() + 4;
    uint32_t count;
    memcpy(&count, p, 4);
    p += 4;
    for (uint32_t i = 0; i < count; ++i) {
      uint32_t type, ndim;
      memcpy(&type, p, 4);
      p += 4;
      memcpy(&ndim, p, 4);
      p += 4;
      std::vector<int64_t> dims(ndim);
      memcpy(dims.data(), p, ndim * 8);
      p += ndim * 8;
      uint64_t nbytes;
      memcpy(&nbytes, p, 8);
      p += 8;
      PJRT_Buffer* buf = Upload(p, static_cast<int32_t>(type), dims);
      p += nbytes;
      if (!buf) return false;
      weight_bufs.push_back(buf);
    }
    return true;
  }

  bool Run(const std::vector<Tensor>& inputs) {
    for (auto* b : outputs) DestroyBuffer(b);
    outputs.clear();

    std::vector<PJRT_Buffer*> args_bufs = weight_bufs;
    std::vector<PJRT_Buffer*> fresh;
    for (const auto& t : inputs) {
      PJRT_Buffer* b = Upload(t.data.data(), t.type, t.dims);
      if (!b) {
        for (auto* f : fresh) DestroyBuffer(f);
        return false;
      }
      args_bufs.push_back(b);
      fresh.push_back(b);
    }

    PJRT_Executable* raw = nullptr;
    {
      PJRT_LoadedExecutable_GetExecutable_Args g;
      memset(&g, 0, sizeof(g));
      g.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
      g.loaded_executable = exec;
      if (!Check(api->PJRT_LoadedExecutable_GetExecutable(&g), "get-exec"))
        return false;
      raw = g.executable;
    }
    size_t n_out = 0;
    {
      PJRT_Executable_NumOutputs_Args n;
      memset(&n, 0, sizeof(n));
      n.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
      n.executable = raw;
      if (!Check(api->PJRT_Executable_NumOutputs(&n), "num-outputs"))
        return false;
      n_out = n.num_outputs;
    }

    std::vector<PJRT_Buffer*> out_list(n_out, nullptr);
    PJRT_Buffer* const* arg_lists[1] = {args_bufs.data()};
    PJRT_Buffer** out_lists[1] = {out_list.data()};
    PJRT_Event* done[1] = {nullptr};

    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    PJRT_LoadedExecutable_Execute_Args ex;
    memset(&ex, 0, sizeof(ex));
    ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ex.executable = exec;
    ex.options = &opts;
    ex.argument_lists = arg_lists;
    ex.num_devices = 1;
    ex.num_args = args_bufs.size();
    ex.output_lists = out_lists;
    ex.device_complete_events = done;
    bool ok = Check(api->PJRT_LoadedExecutable_Execute(&ex), "execute");
    if (ok) ok = Await(done[0], "execute-wait");
    for (auto* b : fresh) DestroyBuffer(b);
    if (!ok) return false;
    outputs.assign(out_list.begin(), out_list.end());
    return true;
  }

  long OutputBytes(int i) {
    PJRT_Buffer_ToHostBuffer_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    a.src = outputs[i];
    a.dst = nullptr;  // size query
    if (!Check(api->PJRT_Buffer_ToHostBuffer(&a), "output-size")) return -1;
    return static_cast<long>(a.dst_size);
  }

  bool CopyOutput(int i, void* dst, long size) {
    PJRT_Buffer_ToHostBuffer_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    a.src = outputs[i];
    a.dst = dst;
    a.dst_size = static_cast<size_t>(size);
    if (!Check(api->PJRT_Buffer_ToHostBuffer(&a), "output-copy"))
      return false;
    return Await(a.event, "output-copy-wait");
  }

  void DestroyBuffer(PJRT_Buffer* b) {
    if (!b) return;
    PJRT_Buffer_Destroy_Args d;
    memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = b;
    api->PJRT_Buffer_Destroy(&d);
  }

  ~PdPredictor() {
    for (auto* b : outputs) DestroyBuffer(b);
    for (auto* b : weight_bufs) DestroyBuffer(b);
    if (exec) {
      PJRT_LoadedExecutable_Destroy_Args d;
      memset(&d, 0, sizeof(d));
      d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      d.executable = exec;
      api->PJRT_LoadedExecutable_Destroy(&d);
    }
    if (client) {
      PJRT_Client_Destroy_Args d;
      memset(&d, 0, sizeof(d));
      d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      d.client = client;
      api->PJRT_Client_Destroy(&d);
    }
  }
};

// ---- C ABI ----
extern "C" {

PdPredictor* pd_predictor_create(const char* prefix, const char* plugin) {
  auto* p = new PdPredictor();
  if (!p->Load(prefix, plugin)) {
    delete p;
    return nullptr;
  }
  return p;
}

int pd_predictor_run(PdPredictor* p, const void** input_ptrs,
                     const int32_t* types, const int64_t* all_dims,
                     const int32_t* ndims, int n_inputs) {
  std::vector<Tensor> ins(n_inputs);
  const int64_t* dp = all_dims;
  for (int i = 0; i < n_inputs; ++i) {
    ins[i].type = types[i];
    ins[i].dims.assign(dp, dp + ndims[i]);
    dp += ndims[i];
    size_t bytes = ins[i].elems() * TypeBytes(types[i]);
    ins[i].data.assign(static_cast<const char*>(input_ptrs[i]), bytes);
  }
  return p->Run(ins) ? 0 : 1;
}

int pd_predictor_num_outputs(PdPredictor* p) {
  return static_cast<int>(p->outputs.size());
}

long pd_predictor_output_bytes(PdPredictor* p, int i) {
  return p->OutputBytes(i);
}

int pd_predictor_copy_output(PdPredictor* p, int i, void* dst, long size) {
  return p->CopyOutput(i, dst, size) ? 0 : 1;
}

void pd_predictor_destroy(PdPredictor* p) { delete p; }

}  // extern "C"

// ---- CLI: predictor_cli <model_prefix> <plugin.so> [input.bin ...] ----
// inputs default to zeros with the shapes in <prefix>.pdmodel.json; outputs
// are written to <prefix>.out<i>.bin and a checksum line is printed.
#ifdef PD_PREDICTOR_MAIN
#include <cmath>

static bool ParseMetaInputs(const std::string& meta_json,
                            std::vector<Tensor>* inputs) {
  // minimal parse of "inputs":[{"shape":[..],"pjrt_type":N},...]
  size_t pos = meta_json.find("\"inputs\"");
  if (pos == std::string::npos) return false;
  size_t end = meta_json.find(']', meta_json.rfind(
      '}', meta_json.find("\"input_names\"")));
  std::string section = meta_json.substr(pos, end - pos);
  size_t off = 0;
  while ((off = section.find("\"shape\"", off)) != std::string::npos) {
    Tensor t;
    size_t lb = section.find('[', off), rb = section.find(']', lb);
    std::string dims = section.substr(lb + 1, rb - lb - 1);
    char* s = dims.data();
    while (*s) {
      t.dims.push_back(strtoll(s, &s, 10));
      while (*s == ',' || *s == ' ') ++s;
    }
    size_t tp = section.find("\"pjrt_type\"", off);
    t.type = static_cast<int32_t>(
        strtol(section.c_str() + section.find(':', tp) + 1, nullptr, 10));
    inputs->push_back(std::move(t));
    off = rb;
  }
  return !inputs->empty();
}

int main(int argc, char** argv) {
  if (argc < 3) {
    fprintf(stderr, "usage: %s <model_prefix> <pjrt_plugin.so> "
                    "[input.bin ...]\n", argv[0]);
    return 2;
  }
  std::string prefix = argv[1];
  PdPredictor* p = pd_predictor_create(argv[1], argv[2]);
  if (!p) {
    fprintf(stderr, "FAILED to create predictor\n");
    return 1;
  }
  std::vector<Tensor> inputs;
  std::string meta = ReadFile(prefix + ".pdmodel.json");
  if (!ParseMetaInputs(meta, &inputs)) {
    fprintf(stderr, "FAILED to parse %s.pdmodel.json\n", argv[1]);
    return 1;
  }
  for (size_t i = 0; i < inputs.size(); ++i) {
    size_t bytes = inputs[i].elems() * TypeBytes(inputs[i].type);
    if (static_cast<int>(i) + 3 < argc) {
      inputs[i].data = ReadFile(argv[i + 3]);
      if (inputs[i].data.size() != bytes) {
        fprintf(stderr, "input %zu: expected %zu bytes got %zu\n", i, bytes,
                inputs[i].data.size());
        return 1;
      }
    } else {
      inputs[i].data.assign(bytes, '\0');
    }
  }
  if (!p->Run(inputs)) {
    fprintf(stderr, "FAILED to run\n");
    return 1;
  }
  int n_out = pd_predictor_num_outputs(p);
  printf("{\"num_outputs\": %d, \"outputs\": [", n_out);
  for (int i = 0; i < n_out; ++i) {
    long bytes = pd_predictor_output_bytes(p, i);
    std::string host(bytes, '\0');
    if (pd_predictor_copy_output(p, i, host.data(), bytes) != 0) return 1;
    std::string out_path = prefix + ".out" + std::to_string(i) + ".bin";
    std::ofstream f(out_path, std::ios::binary);
    f.write(host.data(), bytes);
    // f32 checksum for the test harness
    double sum = 0.0;
    if (bytes % 4 == 0) {
      const float* fp = reinterpret_cast<const float*>(host.data());
      for (long j = 0; j < bytes / 4; ++j) sum += fp[j];
    }
    printf("%s{\"bytes\": %ld, \"f32_sum\": %.6f}", i ? ", " : "", bytes,
           sum);
  }
  printf("]}\n");
  pd_predictor_destroy(p);
  return 0;
}
#endif  // PD_PREDICTOR_MAIN
