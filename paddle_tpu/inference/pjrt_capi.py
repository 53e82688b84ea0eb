"""ctypes wrapper over the native PJRT predictor (csrc/pjrt_predictor.cc).

This is a CONVENIENCE shim for tests and Python callers; the .so itself
is Python-free (links no libpython) — a C++ server embeds it directly
through the PTPU_* C ABI, the deployment shape of the reference's
AnalysisPredictor C API (capi_exp/pd_inference_api.h).
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional

import numpy as np

_META_TO_NP = {
    "f32": np.float32, "f64": np.float64, "f16": np.float16,
    "s8": np.int8, "s16": np.int16, "s32": np.int32, "s64": np.int64,
    "u8": np.uint8, "u16": np.uint16, "u32": np.uint32, "u64": np.uint64,
    "pred": np.bool_,
    # bf16 copies out as raw uint16 words unless ml_dtypes is available
}


def default_plugin() -> str:
    """The PJRT plugin of the installed ``libtpu`` package."""
    import libtpu
    return libtpu.get_library_path()


def _np_dtype(meta_dtype: str):
    if meta_dtype == "bf16":
        try:
            import ml_dtypes
            return np.dtype(ml_dtypes.bfloat16)
        except ImportError:
            return np.dtype(np.uint16)
    return np.dtype(_META_TO_NP[meta_dtype])


def _parse_meta(bundle_dir: str):
    ins, outs = [], []
    with open(os.path.join(bundle_dir, "meta.txt")) as f:
        for line in f:
            parts = line.split()
            if parts and parts[0] in ("in", "out"):
                name, dt, rank = parts[1], parts[2], int(parts[3])
                shape = tuple(int(d) for d in parts[4:4 + rank])
                (ins if parts[0] == "in" else outs).append((name, dt, shape))
    return ins, outs


def _default_lib_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "_lib",
        "libpaddle_tpu_pjrt_predictor.so")


class PjrtPredictor:
    def __init__(self, bundle_dir: str, plugin_path: Optional[str] = None,
                 lib_path: Optional[str] = None):
        plugin_path = plugin_path or default_plugin()
        self._lib = ctypes.CDLL(lib_path or _default_lib_path())
        lib = self._lib
        lib.PTPU_PredictorCreate.restype = ctypes.c_void_p
        lib.PTPU_PredictorCreate.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_size_t]
        lib.PTPU_PredictorRun.restype = ctypes.c_int
        lib.PTPU_PredictorRun.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_char_p, ctypes.c_size_t]
        lib.PTPU_PredictorOutputByteSize.restype = ctypes.c_size_t
        lib.PTPU_PredictorOutputByteSize.argtypes = [ctypes.c_void_p,
                                                     ctypes.c_size_t]
        lib.PTPU_PredictorOutputCopy.restype = ctypes.c_int
        lib.PTPU_PredictorOutputCopy.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_size_t]
        lib.PTPU_PredictorNumInputs.restype = ctypes.c_size_t
        lib.PTPU_PredictorNumInputs.argtypes = [ctypes.c_void_p]
        lib.PTPU_PredictorNumOutputs.restype = ctypes.c_size_t
        lib.PTPU_PredictorNumOutputs.argtypes = [ctypes.c_void_p]
        lib.PTPU_PredictorDestroy.argtypes = [ctypes.c_void_p]

        err = ctypes.create_string_buffer(4096)
        self._h = lib.PTPU_PredictorCreate(
            bundle_dir.encode(), plugin_path.encode(), err, len(err))
        if not self._h:
            raise RuntimeError(
                f"PTPU_PredictorCreate failed: {err.value.decode()}")
        self._in_specs, self._out_specs = _parse_meta(bundle_dir)

    def run(self, inputs: List[np.ndarray]) -> List[np.ndarray]:
        if len(inputs) != len(self._in_specs):
            raise ValueError(f"expected {len(self._in_specs)} inputs")
        arrs = []
        for a, (name, dt, shape) in zip(inputs, self._in_specs):
            arr = np.ascontiguousarray(np.asarray(a, dtype=_np_dtype(dt)))
            if tuple(arr.shape) != shape:
                raise ValueError(
                    f"input '{name}': expected shape {shape}, "
                    f"got {tuple(arr.shape)}")
            arrs.append(arr)
        ptrs = (ctypes.c_void_p * len(arrs))(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in arrs])
        err = ctypes.create_string_buffer(4096)
        rc = self._lib.PTPU_PredictorRun(self._h, ptrs, err, len(err))
        if rc != 0:
            raise RuntimeError(f"PTPU_PredictorRun: {err.value.decode()}")
        outs = []
        for i, (name, dt, shape) in enumerate(self._out_specs):
            nbytes = self._lib.PTPU_PredictorOutputByteSize(self._h, i)
            buf = np.empty(nbytes, np.uint8)
            rc = self._lib.PTPU_PredictorOutputCopy(
                self._h, i, buf.ctypes.data_as(ctypes.c_void_p), nbytes)
            if rc != 0:
                raise RuntimeError(f"output copy failed for '{name}'")
            outs.append(buf.view(_np_dtype(dt)).reshape(shape))
        return outs

    def close(self):
        if getattr(self, "_h", None):
            self._lib.PTPU_PredictorDestroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass  # interpreter teardown: the ctypes lib/handle may be
            #       half-collected; raising from __del__ only prints noise
