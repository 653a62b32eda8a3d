"""Model/tensor C API surface (reference: flexflow_c.h model-building half):
a C caller (driven here through ctypes, exactly as a C program would link)
builds the graph, runs the native search, exports the spec, and the Python
runtime trains it."""
import ctypes
import json

import numpy as np
import pytest

from flexflow_tpu import native


def _lib():
    try:
        path = native.ensure_built()
    except native.NativeBuildError as e:
        pytest.skip(f"native core unavailable: {e}")
    lib = ctypes.CDLL(path)
    lib.ffc_model_create.argtypes = [ctypes.c_int]
    lib.ffc_model_create.restype = ctypes.c_void_p
    lib.ffc_model_destroy.argtypes = [ctypes.c_void_p]
    lib.ffc_model_last_error.argtypes = [ctypes.c_void_p]
    lib.ffc_model_last_error.restype = ctypes.c_char_p
    lib.ffc_tensor_create.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                      ctypes.POINTER(ctypes.c_int64),
                                      ctypes.c_char_p]
    lib.ffc_tensor_create.restype = ctypes.c_int64
    lib.ffc_op.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                           ctypes.POINTER(ctypes.c_int64), ctypes.c_char_p]
    lib.ffc_op.restype = ctypes.c_int64
    lib.ffc_tensor_ndims.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.POINTER(ctypes.c_int64),
                                     ctypes.c_int]
    lib.ffc_tensor_ndims.restype = ctypes.c_int
    lib.ffc_model_export_json.argtypes = [ctypes.c_void_p]
    lib.ffc_model_export_json.restype = ctypes.c_void_p
    lib.ffc_model_optimize.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.c_int, ctypes.c_double]
    lib.ffc_model_optimize.restype = ctypes.c_void_p
    lib.ffc_free.argtypes = [ctypes.c_void_p]
    return lib


def _take_string(lib, ptr):
    s = ctypes.string_at(ptr).decode()
    lib.ffc_free(ptr)
    return s


def _dims(vals):
    return (ctypes.c_int64 * len(vals))(*vals)


def _guids(vals):
    return (ctypes.c_int64 * len(vals))(*vals)


def _build_mlp(lib, batch=8):
    h = lib.ffc_model_create(batch)
    x = lib.ffc_tensor_create(h, 2, _dims([batch, 32]), b"float32")
    assert x > 0
    t = lib.ffc_op(h, b"dense", 1, _guids([x]), b"out_dim=64;activation=relu")
    assert t > 0, lib.ffc_model_last_error(h)
    t = lib.ffc_op(h, b"dense", 1, _guids([t]), b"out_dim=16")
    t = lib.ffc_op(h, b"softmax", 1, _guids([t]), b"")
    assert t > 0
    return h, t


def test_c_api_builds_infers_shapes_and_optimizes():
    lib = _lib()
    h, out = _build_mlp(lib)
    dims = (ctypes.c_int64 * 4)()
    n = lib.ffc_tensor_ndims(h, out, dims, 4)
    assert n == 2 and list(dims[:2]) == [8, 16]

    result = _take_string(lib, lib.ffc_model_optimize(h, 8, 4, 1.2))
    assert result.startswith("cost "), result
    assert "mesh " in result and "strategy " in result
    lib.ffc_model_destroy(h)


def test_c_api_error_reporting():
    lib = _lib()
    h = lib.ffc_model_create(8)
    bad = lib.ffc_op(h, b"warp_drive", 0, _guids([]), b"")
    assert bad == -1
    assert b"warp_drive" in lib.ffc_model_last_error(h)
    lib.ffc_model_destroy(h)


def test_c_built_model_trains_in_python_runtime():
    lib = _lib()
    h, _ = _build_mlp(lib)
    spec = _take_string(lib, lib.ffc_model_export_json(h))
    lib.ffc_model_destroy(h)
    doc = json.loads(spec)
    assert doc["format"] == "flexflow_tpu_c_model"

    import flexflow_tpu as ff
    from flexflow_tpu.native.c_model import model_from_spec

    model = model_from_spec(doc)
    model.compile(optimizer=ff.AdamOptimizer(model, alpha=1e-3),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[ff.MetricsType.METRICS_ACCURACY])
    x = np.random.RandomState(0).randn(8, 32).astype(np.float32)
    y = np.zeros((8, 1), dtype=np.int32)
    hist = model.fit([x], y, batch_size=8, epochs=1)
    assert np.isfinite(hist[0]["loss"])
