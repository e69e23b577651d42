/* pjrt_serve — Python-FREE serving loader over the PJRT C API
 * (ref src/c_api/c_predict_api.cc: the reference's no-frontend deployment
 * path; here the artifact is the StableHLO module exported by
 * contrib/serving.py and the "runtime" is any PJRT plugin .so).
 *
 * This is a plain C program: no Python, no C++, no framework libraries —
 * only dlopen + the vendored stable pjrt_c_api.h. It demonstrates the
 * claim in contrib/serving.py that the .mxtpu payload's StableHLO module
 * is consumable by any PJRT plugin through the PJRT C API (the contract
 * TF-Serving/IFRT production loaders use).
 *
 *   pjrt_serve <plugin.so> <module.mlir> <compile_options.pb> \
 *              <input.f32.bin> <output.f32.bin> <d0,d1,...>
 *
 * Pipeline: dlopen plugin -> GetPjrtApi -> PJRT_Plugin_Initialize ->
 * PJRT_Client_Create -> PJRT_Client_Compile("mlir") ->
 * BufferFromHostBuffer -> LoadedExecutable_Execute -> Buffer_ToHostBuffer.
 *
 * The plugin for a TPU host is libtpu.so (python -c "import libtpu;
 * print(libtpu.get_library_path())"); it exports GetPjrtApi. There is no
 * CPU PJRT plugin .so (jaxlib's CPU client is linked into its Python
 * extension), and loading libtpu.so off a TPU host spends minutes in
 * metadata retries, so no test builds or runs this file today; it has
 * not been run against the installed libtpu. ROADMAP D9 decides whether
 * it loads the v2 artifact or goes.
 */
#include <dlfcn.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include "../incubator_mxnet_tpu/native/third_party/pjrt_c_api.h"

static const PJRT_Api* g_api;

static void die(const char* where, PJRT_Error* err) {
  if (!err) {
    fprintf(stderr, "FAIL %s\n", where);
    exit(1);
  }
  PJRT_Error_Message_Args m;
  memset(&m, 0, sizeof(m));
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = err;
  g_api->PJRT_Error_Message(&m);
  fprintf(stderr, "FAIL %s: %.*s\n", where, (int)m.message_size, m.message);
  PJRT_Error_Destroy_Args d;
  memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = err;
  g_api->PJRT_Error_Destroy(&d);
  exit(1);
}

#define CHECK(where, expr)        \
  do {                            \
    PJRT_Error* _e = (expr);      \
    if (_e) die(where, _e);       \
  } while (0)

static char* read_file(const char* path, size_t* out_size) {
  FILE* f = fopen(path, "rb");
  if (!f) {
    fprintf(stderr, "FAIL open %s\n", path);
    exit(1);
  }
  fseek(f, 0, SEEK_END);
  long n = ftell(f);
  fseek(f, 0, SEEK_SET);
  char* buf = (char*)malloc((size_t)n + 1);
  if (!buf || fread(buf, 1, (size_t)n, f) != (size_t)n) {
    fprintf(stderr, "FAIL read %s\n", path);
    exit(1);
  }
  buf[n] = 0;
  fclose(f);
  *out_size = (size_t)n;
  return buf;
}

static void await_event(PJRT_Event* ev, const char* where) {
  PJRT_Event_Await_Args aw;
  memset(&aw, 0, sizeof(aw));
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  CHECK(where, g_api->PJRT_Event_Await(&aw));
  PJRT_Event_Destroy_Args ed;
  memset(&ed, 0, sizeof(ed));
  ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  ed.event = ev;
  CHECK(where, g_api->PJRT_Event_Destroy(&ed));
}

int main(int argc, char** argv) {
  if (argc != 7) {
    fprintf(stderr,
            "usage: %s <plugin.so> <module.mlir> <options.pb> <in.bin> "
            "<out.bin> <d0,d1,...>\n",
            argv[0]);
    return 2;
  }
  const char* plugin = argv[1];

  /* ---- plugin handshake ------------------------------------------- */
  void* so = dlopen(plugin, RTLD_NOW | RTLD_LOCAL);
  if (!so) {
    fprintf(stderr, "FAIL dlopen: %s\n", dlerror());
    return 1;
  }
  typedef const PJRT_Api* (*GetPjrtApiFn)(void);
  GetPjrtApiFn get_api = (GetPjrtApiFn)dlsym(so, "GetPjrtApi");
  if (!get_api) {
    fprintf(stderr, "FAIL no GetPjrtApi in %s\n", plugin);
    return 1;
  }
  g_api = get_api();
  if (!g_api) {
    fprintf(stderr, "FAIL GetPjrtApi returned NULL\n");
    return 1;
  }
  printf("PJRT api %d.%d struct_size=%zu\n",
         g_api->pjrt_api_version.major_version,
         g_api->pjrt_api_version.minor_version, g_api->struct_size);
  /* Version handshake (pjrt_c_api.h forward-compat contract): a MAJOR
   * mismatch means incompatible struct layouts — refuse. MINOR skew is
   * fine in either direction: fields are append-only, callers pass
   * struct_size, and a plugin ignores trailing fields it predates. */
  if (g_api->pjrt_api_version.major_version != PJRT_API_MAJOR) {
    fprintf(stderr, "FAIL plugin PJRT major %d != header major %d\n",
            g_api->pjrt_api_version.major_version, PJRT_API_MAJOR);
    return 1;
  }

  PJRT_Plugin_Initialize_Args init;
  memset(&init, 0, sizeof(init));
  init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  CHECK("Plugin_Initialize", g_api->PJRT_Plugin_Initialize(&init));
  printf("HANDSHAKE OK\n");
  if (getenv("PJRT_SERVE_HANDSHAKE_ONLY")) return 0;

  /* ---- client ------------------------------------------------------ */
  /* Plugin-specific create options come from PJRT_SERVE_OPTIONS:
   * semicolon-separated name=TYPEvalue pairs where TYPE is 'i' (int64)
   * or 's' (string), e.g. "some_flag=i1;some_name=svalue". libtpu.so
   * needs none. */
  PJRT_NamedValue nvs[32];
  size_t num_nvs = 0;
  char* optspec = getenv("PJRT_SERVE_OPTIONS")
                      ? strdup(getenv("PJRT_SERVE_OPTIONS")) : NULL;
  if (optspec) {
    for (char* save = NULL, * tok = strtok_r(optspec, ";", &save);
         tok && num_nvs < 32; tok = strtok_r(NULL, ";", &save)) {
      char* eq = strchr(tok, '=');
      if (!eq || !eq[1]) continue;
      *eq = 0;
      PJRT_NamedValue* nv = &nvs[num_nvs++];
      memset(nv, 0, sizeof(*nv));
      nv->struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv->name = tok;
      nv->name_size = strlen(tok);
      if (eq[1] == 'i') {
        nv->type = PJRT_NamedValue_kInt64;
        nv->int64_value = atoll(eq + 2);
        nv->value_size = 1;
      } else {  /* 's' */
        nv->type = PJRT_NamedValue_kString;
        nv->string_value = eq + 2;
        nv->value_size = strlen(eq + 2);
      }
    }
  }

  PJRT_Client_Create_Args cc;
  memset(&cc, 0, sizeof(cc));
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cc.create_options = num_nvs ? nvs : NULL;
  cc.num_options = num_nvs;
  CHECK("Client_Create", g_api->PJRT_Client_Create(&cc));
  PJRT_Client* client = cc.client;

  PJRT_Client_AddressableDevices_Args ad;
  memset(&ad, 0, sizeof(ad));
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = client;
  CHECK("AddressableDevices", g_api->PJRT_Client_AddressableDevices(&ad));
  if (ad.num_addressable_devices == 0) {
    fprintf(stderr, "FAIL no addressable devices\n");
    return 1;
  }
  PJRT_Device* dev = ad.addressable_devices[0];
  printf("devices=%zu\n", ad.num_addressable_devices);

  /* ---- compile the StableHLO module ------------------------------- */
  size_t code_size, opts_size;
  char* code = read_file(argv[2], &code_size);
  char* opts = read_file(argv[3], &opts_size);

  PJRT_Program prog;
  memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = code;
  prog.code_size = code_size;
  prog.format = "mlir";
  prog.format_size = 4;

  PJRT_Client_Compile_Args comp;
  memset(&comp, 0, sizeof(comp));
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = client;
  comp.program = &prog;
  comp.compile_options = opts;
  comp.compile_options_size = opts_size;
  CHECK("Client_Compile", g_api->PJRT_Client_Compile(&comp));
  PJRT_LoadedExecutable* exec = comp.executable;
  printf("COMPILE OK\n");

  /* ---- stage the input -------------------------------------------- */
  size_t in_size;
  char* in_data = read_file(argv[4], &in_size);
  int64_t dims[16];
  size_t ndims = 0;
  size_t nelems = 1;
  {
    char* spec = strdup(argv[6]);
    for (char* tok = strtok(spec, ","); tok; tok = strtok(NULL, ",")) {
      if (ndims >= 16) {
        fprintf(stderr, "FAIL more than 16 dims in %s\n", argv[6]);
        return 1;
      }
      dims[ndims] = atoll(tok);
      nelems *= (size_t)dims[ndims];
      ++ndims;
    }
    free(spec);
  }
  if (in_size != nelems * sizeof(float)) {
    fprintf(stderr,
            "FAIL input %s holds %zu bytes but shape %s needs %zu\n",
            argv[4], in_size, argv[6], nelems * sizeof(float));
    return 1;
  }

  PJRT_Client_BufferFromHostBuffer_Args hb;
  memset(&hb, 0, sizeof(hb));
  hb.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  hb.client = client;
  hb.data = in_data;
  hb.type = PJRT_Buffer_Type_F32;
  hb.dims = dims;
  hb.num_dims = ndims;
  hb.host_buffer_semantics = PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  hb.device = dev;
  CHECK("BufferFromHostBuffer", g_api->PJRT_Client_BufferFromHostBuffer(&hb));
  await_event(hb.done_with_host_buffer, "host buffer transfer");
  PJRT_Buffer* in_buf = hb.buffer;

  /* ---- execute ----------------------------------------------------- */
  /* size the output list from the executable itself — the plugin writes
   * num_outputs entries into whatever the caller hands it */
  PJRT_LoadedExecutable_GetExecutable_Args ge;
  memset(&ge, 0, sizeof(ge));
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.loaded_executable = exec;
  CHECK("GetExecutable", g_api->PJRT_LoadedExecutable_GetExecutable(&ge));
  PJRT_Executable_NumOutputs_Args no;
  memset(&no, 0, sizeof(no));
  no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  no.executable = ge.executable;
  CHECK("NumOutputs", g_api->PJRT_Executable_NumOutputs(&no));

  PJRT_ExecuteOptions eopts;
  memset(&eopts, 0, sizeof(eopts));
  eopts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_Buffer* const arg_list[] = {in_buf};
  PJRT_Buffer* const* const arg_lists[] = {arg_list};
  PJRT_Buffer** out_list =
      (PJRT_Buffer**)calloc(no.num_outputs ? no.num_outputs : 1,
                            sizeof(PJRT_Buffer*));
  PJRT_Buffer** const out_lists[] = {out_list};
  PJRT_Event* done[1] = {0};

  PJRT_LoadedExecutable_Execute_Args ex;
  memset(&ex, 0, sizeof(ex));
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = exec;
  ex.options = &eopts;
  ex.argument_lists = arg_lists;
  ex.num_devices = 1;
  ex.num_args = 1;
  ex.output_lists = out_lists;
  ex.device_complete_events = done;
  CHECK("Execute", g_api->PJRT_LoadedExecutable_Execute(&ex));
  await_event(done[0], "execute");
  printf("EXECUTE OK\n");

  /* ---- fetch the output ------------------------------------------- */
  PJRT_Buffer_ToHostBuffer_Args th;
  memset(&th, 0, sizeof(th));
  th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  th.src = out_list[0];
  CHECK("ToHostBuffer(size)", g_api->PJRT_Buffer_ToHostBuffer(&th));
  char* out = (char*)malloc(th.dst_size);
  th.dst = out;
  CHECK("ToHostBuffer", g_api->PJRT_Buffer_ToHostBuffer(&th));
  await_event(th.event, "device->host copy");

  FILE* f = fopen(argv[5], "wb");
  if (!f || fwrite(out, 1, th.dst_size, f) != th.dst_size) {
    fprintf(stderr, "FAIL write %s\n", argv[5]);
    return 1;
  }
  fclose(f);
  printf("PJRT SERVE OK %zu bytes\n", th.dst_size);
  return 0;
}
