// Predictor: cgo binding over the C inference API (reference
// go/paddle/predictor.go wraps paddle_c_api.h the same way).
//
// Build (the shared library is built from source into csrc/build, and
// embeds CPython, so link python too):
//
//	make -C ${REPO}/csrc build/libpd_infer_capi.so
//	CGO_CFLAGS="-I${REPO}/csrc" \
//	CGO_LDFLAGS="-L${REPO}/csrc/build -lpd_infer_capi -lpython3.12" \
//	go build ./...
package paddle

/*
#include <stdlib.h>
#include "pd_c_api.h"
*/
import "C"

import (
	"errors"
	"runtime"
	"unsafe"
)

// Predictor wraps the opaque PD_Predictor handle.
type Predictor struct {
	handle *C.PD_Predictor
}

// NewPredictor creates a predictor from the config's model prefix.
func NewPredictor(cfg *Config) (*Predictor, error) {
	cs := C.CString(cfg.Model())
	defer C.free(unsafe.Pointer(cs))
	h := C.PD_NewPredictor(cs)
	if h == nil {
		return nil, errors.New(C.GoString(C.PD_GetLastError()))
	}
	p := &Predictor{handle: h}
	runtime.SetFinalizer(p, (*Predictor).Delete)
	return p, nil
}

// Run executes the model on one input tensor and returns the first output.
func (p *Predictor) Run(input *Tensor) (*Tensor, error) {
	if p.handle == nil {
		return nil, errors.New("predictor already deleted")
	}
	var outData *C.float
	var outShape [8]C.int64_t
	var outNdim C.int
	rc := C.PD_PredictorRun(
		p.handle,
		(*C.float)(unsafe.Pointer(&input.Data[0])),
		(*C.int64_t)(unsafe.Pointer(&input.Shape[0])),
		C.int(len(input.Shape)),
		&outData, &outShape[0], &outNdim)
	if rc != 0 {
		return nil, errors.New(C.GoString(C.PD_GetLastError()))
	}
	defer C.PD_FreeBuffer(unsafe.Pointer(outData))
	shape := make([]int64, int(outNdim))
	n := int64(1)
	for i := range shape {
		shape[i] = int64(outShape[i])
		n *= shape[i]
	}
	data := make([]float32, n)
	src := unsafe.Slice((*float32)(unsafe.Pointer(outData)), n)
	copy(data, src)
	return &Tensor{Shape: shape, Data: data}, nil
}

// Delete releases the native predictor. Safe to call twice.
func (p *Predictor) Delete() {
	if p.handle != nil {
		C.PD_DeletePredictor(p.handle)
		p.handle = nil
	}
}
