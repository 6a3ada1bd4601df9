"""int8 weight-only matmul for XLA-CPU via an FFI custom call.

XLA-CPU cannot read int8 weights inside a dot: its lowering materializes
the dequantized f32 array first, so an int8-quantized model streams
f32-sized bytes per decode step and the quantization buys nothing on the
CPU platform. This wraps ``native/src/qgemv.cc`` — a C++
kernel that streams the weights int8 and dequantizes in registers — as a
jit-compatible ``jax.ffi`` call, the CPU sibling of the Pallas int4
fused-unpack kernel (ops/pallas/quant_matmul.py) on the TPU side.

The kernels run over a persistent row-partitioned thread pool inside the
native lib (qgemv.cc RowPool): decode is weight-streaming-bound and one
core's bandwidth is the single-thread ceiling, so output channels split
into contiguous per-thread ranges. ``DLI_NATIVE_THREADS`` sets the count
(default: all cores — native.configured_threads); ``set_threads`` resizes
a live process. Results are bitwise identical across thread counts: a row
is computed start-to-finish by exactly one thread.

Built on first use with g++ (same pattern as native/__init__.py's block
pool); if the toolchain or ``jax.ffi`` is unavailable, ``available()``
is False and callers keep the portable XLA path. The reference has no
counterpart at any level — its CPU path is stock HF torch generate
(reference worker/app.py:297-305).

Weight layout: the kernel wants the TRANSPOSED quantized weight
``[dout, din]`` (contiguous along the contraction axis). The engine
repacks int8 leaves into this layout when it adopts the CPU-unrolled
path (runtime/engine.py _maybe_unroll_layers); the per-row int8
embedding table (ops/quant.py quantize_embed) is already ``[V, D]`` and
needs no repack for the tied unembed.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import tempfile
import threading

from jax import ffi

log = logging.getLogger("dli.cpu_gemv")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "native", "src", "qgemv.cc")
_LIB = os.path.join(os.path.dirname(_HERE), "native", "libdli_qgemv.so")
# ThreadSanitizer build (scripts/check.sh --tsan): separate artifact so
# the instrumented and plain builds never clobber each other's mtime
# freshness check
_LIB_TSAN = os.path.join(os.path.dirname(_HERE), "native",
                         "libdli_qgemv_tsan.so")
_TARGET = "dli_qgemv_i8"


def tsan_requested() -> bool:
    """``DLI_NATIVE_TSAN=1`` builds/loads the ``-fsanitize=thread -g``
    variant of the RowPool kernel. The TSan *runtime* must be present in
    the process (run python under ``LD_PRELOAD=libtsan.so``, as
    ``scripts/check.sh --tsan`` does) or the dlopen fails and the whole
    native path reports unavailable — loudly, by design."""
    return os.environ.get("DLI_NATIVE_TSAN", "").lower() in ("1", "true")

_lock = threading.Lock()
_state = {"ready": False, "failed": False}


# the kernel keeps per-row accumulators for up to this many activation
# rows while a weight row is hot in L1; larger M is compute-bound and
# belongs on the XLA dequant matmul (see MAX_FAST_M use in callers)
MAX_FAST_M = 4


def _build():
    tsan = tsan_requested()
    lib_path = _LIB_TSAN if tsan else _LIB
    if (os.path.exists(lib_path)
            and os.path.getmtime(lib_path) >= os.path.getmtime(_SRC)):
        return lib_path
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=os.path.dirname(lib_path))
    os.close(fd)
    obj = tmp + ".o"
    # TSan instruments every load/store in the RowPool (and wants -g so
    # reports carry source lines); -O1 keeps reports honest where -O3's
    # reordering can fold the racing accesses away
    extra = ["-fsanitize=thread", "-g", "-O1"] if tsan else ["-O3"]
    try:
        # fast-math applies at COMPILE only (the dot reassociates/
        # vectorizes); linking without it keeps crtfastmath.o out of the
        # .so — that startup object would flip FTZ/DAZ in MXCSR for the
        # whole process the moment the library loads. -pthread on both
        # steps: the kernel's persistent row pool (qgemv.cc RowPool)
        # needs it, and a lib silently built without it would deadlock
        # on first dispatch.
        subprocess.run(
            ["g++", *extra, "-march=native", "-ffast-math", "-std=c++17",
             "-pthread", "-c", "-fPIC", f"-I{ffi.include_dir()}",
             _SRC, "-o", obj],
            check=True, capture_output=True, timeout=180)
        subprocess.run(
            ["g++", "-shared", "-pthread",
             *(["-fsanitize=thread"] if tsan else []), obj, "-o", tmp],
            check=True, capture_output=True, timeout=60)
        os.rename(tmp, lib_path)  # atomic: concurrent procs never half-load
    finally:
        for p in (tmp, obj):
            if os.path.exists(p):
                os.unlink(p)
    return lib_path


def _ensure():
    if _state["ready"] or _state["failed"]:
        return _state["ready"]
    with _lock:
        if _state["ready"] or _state["failed"]:
            return _state["ready"]
        try:
            lib = ctypes.CDLL(_build())
            ffi.register_ffi_target(
                _TARGET, ffi.pycapsule(lib.QGemvI8), platform="cpu")
            ffi.register_ffi_target(
                "dli_gemv_f32", ffi.pycapsule(lib.GemvF32),
                platform="cpu")
            ffi.register_ffi_target(
                "dli_gemv_bf16", ffi.pycapsule(lib.GemvBf16),
                platform="cpu")
            lib.DliGemvGetThreads.restype = ctypes.c_int
            lib.DliGemvSetThreads.argtypes = [ctypes.c_int]
            _state["lib"] = lib
            _state["ready"] = True
            log.info("cpu gemv kernels ready (threads=%d)",
                     lib.DliGemvGetThreads())
        except Exception as e:  # missing g++ / headers: fall back
            log.warning("cpu int8 gemv unavailable (%s); int8 matmuls use "
                        "the XLA dequant path on cpu", e)
            _state["failed"] = True
    return _state["ready"]


def available() -> bool:
    """True once the kernel is built+registered (attempts on first call)."""
    return _ensure()


def get_threads() -> int:
    """Active row-pool thread count inside the native lib (0 when the
    kernel is unavailable). Initial value honors ``DLI_NATIVE_THREADS``
    (native.configured_threads documents the same default)."""
    if not _ensure():
        return 0
    return int(_state["lib"].DliGemvGetThreads())


def set_threads(n: int) -> int:
    """Resize the native row pool at runtime (n < 1 restores the
    ``DLI_NATIVE_THREADS``/core-count default). Output is bitwise
    identical for ANY setting — each output row stays on one thread —
    so this is purely a throughput/oversubscription knob. Returns the
    applied count (0 when the kernel is unavailable)."""
    if not _ensure():
        return 0
    _state["lib"].DliGemvSetThreads(int(n))
    return int(_state["lib"].DliGemvGetThreads())


def usable_for_rows(rows: int) -> bool:
    """One gate for trace-time call sites that are NOT behind an
    engine-repacked leaf (the tied unembed): decode-shaped row counts,
    single-visible-device CPU process, kernel built. Keeping it here
    stops the condition from drifting between branches."""
    import jax
    return (rows <= MAX_FAST_M
            and jax.default_backend() == "cpu"
            and jax.device_count() == 1
            and available())


def qgemv_i8(x, wt, scale):
    """y[M,N] = (x[M,K] @ dequant(wt[N,K]).T) * scale[N], f32 out.

    Jit-compatible (lowers to the registered custom call). Callers gate on
    ``available()`` and keep M small (<= MAX_FAST_M) — large M is
    compute-bound and faster on the XLA dequant matmul.
    """
    import jax
    import jax.numpy as jnp
    m, _ = x.shape
    n = wt.shape[0]
    call = ffi.ffi_call(
        _TARGET, jax.ShapeDtypeStruct((m, n), jnp.float32))
    return call(x.astype(jnp.float32), wt, scale.astype(jnp.float32))


def gemv_w(x, wt):
    """y[M,N] = x[M,K] @ wt[N,K].T for f32 or bf16-stored weights, f32
    out (f32 accumulate either way). Same caveats as qgemv_i8."""
    import jax
    import jax.numpy as jnp
    m, _ = x.shape
    n = wt.shape[0]
    target = "dli_gemv_bf16" if wt.dtype == jnp.bfloat16 else "dli_gemv_f32"
    call = ffi.ffi_call(
        target, jax.ShapeDtypeStruct((m, n), jnp.float32))
    return call(x.astype(jnp.float32), wt)
