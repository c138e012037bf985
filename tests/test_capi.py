"""Native C inference API (reference `paddle/fluid/inference/capi/`):
a real C program links libpd_infer_capi.so, loads a jit-saved artifact,
runs float32 inference, and its output must match the in-process
predictor.

The library is built from `csrc/inference_capi.cc` into the git-ignored
`csrc/build/` (`paddle_tpu.utils.native.native_lib`) — no binary is
tracked. The environment gate (`_capi_ready`) is deliberate: when the C
toolchain is absent or the build fails on THIS machine, the tests skip
with the exact reason instead of failing."""
import os
import shutil
import subprocess
import textwrap

import numpy as np
import pytest

from paddle_tpu.utils.native import BUILD_DIR, CSRC_DIR, native_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(BUILD_DIR, "libpd_infer_capi.so")

C_DRIVER = r"""
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

typedef struct PD_Predictor PD_Predictor;
PD_Predictor* PD_NewPredictor(const char* model_prefix);
int PD_PredictorRun(PD_Predictor*, const float*, const int64_t*, int,
                    float**, int64_t*, int*);
void PD_DeletePredictor(PD_Predictor*);
void PD_FreeBuffer(void*);
const char* PD_GetLastError(void);

int main(int argc, char** argv) {
  /* argv: model_prefix in_file rows cols out_file */
  const char* prefix = argv[1];
  int64_t shape[2] = {atoll(argv[3]), atoll(argv[4])};
  int64_t n = shape[0] * shape[1];
  float* in = (float*)malloc(n * sizeof(float));
  FILE* f = fopen(argv[2], "rb");
  if (fread(in, sizeof(float), n, f) != (size_t)n) return 10;
  fclose(f);

  PD_Predictor* p = PD_NewPredictor(prefix);
  if (!p) { fprintf(stderr, "new: %s\n", PD_GetLastError()); return 11; }
  float* out = NULL;
  int64_t oshape[8];
  int ondim = 0;
  int rc = PD_PredictorRun(p, in, shape, 2, &out, oshape, &ondim);
  if (rc != 0) {
    fprintf(stderr, "run: %s\n", PD_GetLastError());
    return 12;
  }
  int64_t total = 1;
  for (int i = 0; i < ondim; ++i) total *= oshape[i];
  f = fopen(argv[5], "wb");
  fwrite(&ondim, sizeof(int), 1, f);
  fwrite(oshape, sizeof(int64_t), ondim, f);
  fwrite(out, sizeof(float), total, f);
  fclose(f);
  PD_FreeBuffer(out);
  PD_DeletePredictor(p);
  printf("CAPI_OK\n");
  return 0;
}
"""


_READY = None  # cached (ok, reason) — the build is expensive, run once


def _capi_ready():
    """(ok, skip_reason): toolchain present -> build from source.
    Cached for the whole session."""
    global _READY
    if _READY is not None:
        return _READY
    missing = [t for t in ("gcc", "make") if shutil.which(t) is None]
    if missing:
        _READY = (False, f"C toolchain absent: no {'/'.join(missing)} "
                         f"in this image")
        return _READY
    try:
        native_lib("pd_infer_capi")
    except RuntimeError as e:
        _READY = (False, "C API lib build failed: " + str(e)[-300:])
        return _READY
    _READY = (True, "")
    return _READY


def test_c_program_runs_saved_model(tmp_path):
    ok, why = _capi_ready()
    if not ok:
        pytest.skip(why)
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.static.input_spec import InputSpec

    paddle.seed(4)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 3))
    prefix = str(tmp_path / "model")
    paddle.jit.save(net, prefix, input_spec=[InputSpec([2, 8], "float32")])

    x = np.random.RandomState(5).standard_normal((2, 8)).astype("float32")
    ref = net(paddle.to_tensor(x)).numpy()

    cfile = tmp_path / "driver.c"
    cfile.write_text(textwrap.dedent(C_DRIVER))
    exe = str(tmp_path / "driver")
    r = subprocess.run(
        ["gcc", str(cfile), "-o", exe, f"-L{BUILD_DIR}", "-lpd_infer_capi",
         f"-Wl,-rpath,{BUILD_DIR}"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    in_file = str(tmp_path / "in.bin")
    x.tofile(in_file)
    out_file = str(tmp_path / "out.bin")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run([exe, prefix, in_file, "2", "8", out_file],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr[-1500:])
    assert "CAPI_OK" in r.stdout

    with open(out_file, "rb") as f:
        ondim = np.fromfile(f, dtype=np.int32, count=1)[0]
        oshape = np.fromfile(f, dtype=np.int64, count=ondim)
        out = np.fromfile(f, dtype=np.float32).reshape(oshape)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


class TestLanguageBindings:
    """Go/R bindings (reference `go/paddle/*.go`, `r/`): no Go toolchain or
    R runtime in this image, so validate the bindings statically — every C
    symbol the cgo layer references must exist in the built .so and be
    declared in pd_c_api.h."""

    def _cgo_symbols(self):
        import re
        syms = set()
        go_dir = os.path.join(REPO, "go", "paddle")
        for fn in os.listdir(go_dir):
            if fn.endswith(".go"):
                with open(os.path.join(go_dir, fn)) as f:
                    # function calls only — C.PD_Predictor is a type
                    syms |= set(re.findall(r"C\.(PD_\w+)\(", f.read()))
        return syms

    def test_go_symbols_exist_in_library(self):
        _capi_ready()  # best-effort build; nm only needs the artifact
        if not os.path.exists(LIB):
            pytest.skip("libpd_infer_capi.so not built "
                        "(C toolchain absent or build failed)")
        out = subprocess.run(["nm", "-D", LIB], capture_output=True,
                             text=True, check=True).stdout
        exported = {line.split()[-1] for line in out.splitlines()
                    if " T " in line}
        syms = self._cgo_symbols()
        assert syms, "no C.PD_* references found in go/paddle"
        missing = syms - exported
        assert not missing, f"cgo references unexported symbols: {missing}"

    def test_header_declares_all_symbols(self):
        with open(os.path.join(CSRC_DIR, "pd_c_api.h")) as f:
            header = f.read()
        for sym in self._cgo_symbols():
            assert sym in header, f"{sym} missing from pd_c_api.h"

    def test_r_binding_targets_real_api(self):
        """The R shim drives the same Python inference API the C layer
        embeds; check the functions it calls exist."""
        with open(os.path.join(REPO, "r", "paddle_infer.R")) as f:
            src = f.read()
        assert 'import("paddle_tpu.inference")' in src
        import paddle_tpu.inference as inf
        assert hasattr(inf, "Config") and hasattr(inf, "create_predictor")
