"""Native host runtime bindings.

Loads `libamtpu_core.so` (built from /root/repo/native/) and exposes
`NativeDocPool`: the C++ host runtime driving the JAX device kernels
(register resolution, RGA linearization, dominance indexes), with all
per-op host stages (causal scheduling, columnar encoding, patch emission,
mirror maintenance) in C++ and changes/patches crossing the boundary as
msgpack bytes.

`NativeDocPool.apply_batch(dict)` round-trips through msgpack so the
tests can hold its patches to the scalar oracle (`automerge_tpu.backend`)
dict for dict; `apply_batch_bytes(bytes) -> bytes` is the zero-Python
wire path the sidecar serves.
"""

import ctypes
import os
import re
import subprocess
import sys
import threading
import time

import msgpack
import numpy as np

from .. import faults, telemetry, trace
from ..telemetry import attribution, recorder
from ..utils import patch_map
from ..utils.common import (doc_key, env_bool, env_int, env_raw, env_str,
                            parse_mesh_env)
from ..utils.wire import map_header as _map_header
from ..utils.wire import read_map_header as _read_map_header

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(os.path.dirname(_DIR)), 'native')
# AMTPU_NATIVE_LIB loads an alternate build of the SAME ABI -- the asan
# gate (tools/asan_check.py) points it at the -fsanitize=address,
# undefined .so; an override is trusted as-is (no rebuild)
_LIB_OVERRIDE = env_str('AMTPU_NATIVE_LIB', '')
_LIB_PATH = _LIB_OVERRIDE or os.path.join(_DIR, 'libamtpu_core.so')


def _build():
    """make in native/: its own rules decide what is stale (core.cpp and
    msgpack.h).  A failed build raises with make's output."""
    out = subprocess.run(['make', '-C', _SRC], capture_output=True,
                         text=True)
    if out.returncode != 0:
        raise RuntimeError('native build failed (make rc=%d):\n%s%s'
                           % (out.returncode, out.stdout, out.stderr))


def _load():
    if not _LIB_OVERRIDE and os.path.isdir(_SRC):
        _build()
    lib = ctypes.CDLL(_LIB_PATH)
    lib.amtpu_pool_new.restype = ctypes.c_void_p
    lib.amtpu_pool_free.argtypes = [ctypes.c_void_p]
    lib.amtpu_doc_count.restype = ctypes.c_int64
    lib.amtpu_doc_count.argtypes = [ctypes.c_void_p]
    lib.amtpu_last_error.restype = ctypes.c_char_p
    lib.amtpu_last_error_kind.restype = ctypes.c_int
    lib.amtpu_begin.restype = ctypes.c_void_p
    lib.amtpu_begin.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_int64]
    lib.amtpu_begin_local.restype = ctypes.c_void_p
    lib.amtpu_begin_local.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_int64]
    lib.amtpu_batch_free.argtypes = [ctypes.c_void_p]
    lib.amtpu_batch_rollback.restype = ctypes.c_int
    lib.amtpu_batch_rollback.argtypes = [ctypes.c_void_p]
    lib.amtpu_batch_dims.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_int64)]
    for name in ('g', 't', 'a', 's', 'clocktab', 'clockidx', 'sort',
                 'obj', 'par', 'ctr', 'act', 'linsort', 'memidx'):
        fn = getattr(lib, 'amtpu_col_' + name)
        fn.restype = ctypes.POINTER(ctypes.c_int32)
        fn.argtypes = [ctypes.c_void_p]
    for name in ('d', 'val', 'hostovf'):
        fn = getattr(lib, 'amtpu_col_' + name)
        fn.restype = ctypes.POINTER(ctypes.c_uint8)
        fn.argtypes = [ctypes.c_void_p]
    lib.amtpu_mid.restype = ctypes.c_int
    lib.amtpu_mid.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int]
    lib.amtpu_dom_dims.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                   ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_dom_v0.restype = ctypes.POINTER(ctypes.c_float)
    lib.amtpu_dom_v0.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    for name in ('er', 'oe', 'orank', 'od'):
        fn = getattr(lib, 'amtpu_dom_' + name)
        fn.restype = ctypes.POINTER(ctypes.c_int32)
        fn.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.amtpu_dom_ov.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_dom_ov.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.amtpu_dom_set_indexes.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                          ctypes.POINTER(ctypes.c_int32)]
    lib.amtpu_fused_dims.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_int64)]
    for name in ('ersrc', 'oranksrc', 'domsrc'):
        fn = getattr(lib, 'amtpu_fdom_' + name)
        fn.restype = ctypes.POINTER(ctypes.c_int32)
        fn.argtypes = [ctypes.c_void_p]
    lib.amtpu_mid_fused.restype = ctypes.c_int
    lib.amtpu_mid_fused.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32)]
    lib.amtpu_esc_dims.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_esc_group_meta.restype = ctypes.POINTER(ctypes.c_int64)
    lib.amtpu_esc_group_meta.argtypes = [ctypes.c_void_p]
    lib.amtpu_esc_rows.restype = ctypes.POINTER(ctypes.c_int32)
    lib.amtpu_esc_rows.argtypes = [ctypes.c_void_p]
    lib.amtpu_esc_mem_off.restype = ctypes.POINTER(ctypes.c_int64)
    lib.amtpu_esc_mem_off.argtypes = [ctypes.c_void_p]
    lib.amtpu_esc_mem.restype = ctypes.POINTER(ctypes.c_int32)
    lib.amtpu_esc_mem.argtypes = [ctypes.c_void_p]
    lib.amtpu_resclk_info.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_latch_defaults.argtypes = [ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_resclk_tab.restype = ctypes.POINTER(ctypes.c_int32)
    lib.amtpu_resclk_tab.argtypes = [ctypes.c_void_p]
    lib.amtpu_resclk_batch_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_mid_packed.restype = ctypes.c_int
    lib.amtpu_mid_packed.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
    lib.amtpu_finish.restype = ctypes.c_int
    lib.amtpu_finish.argtypes = [ctypes.c_void_p]
    lib.amtpu_host_dominance.restype = ctypes.c_int
    lib.amtpu_host_dominance.argtypes = [ctypes.c_void_p]
    lib.amtpu_mid_hostreg.restype = ctypes.c_int
    lib.amtpu_mid_hostreg.argtypes = [ctypes.c_void_p]
    lib.amtpu_pool_set_hostfull.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.amtpu_batch_trace.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_double)]
    lib.amtpu_sched_counts.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_dom_obj_meta.restype = ctypes.c_int64
    lib.amtpu_dom_obj_meta.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                       ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_batch_doc_id.restype = ctypes.c_char_p
    lib.amtpu_batch_doc_id.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.amtpu_intern_str.restype = ctypes.c_char_p
    lib.amtpu_intern_str.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.amtpu_arena_raw.restype = ctypes.c_int64
    lib.amtpu_arena_raw.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint32)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
    lib.amtpu_result.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_result.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_get_patch.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_get_patch.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_save.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_save.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_truncate_history.restype = ctypes.c_int64
    lib.amtpu_truncate_history.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int64]
    lib.amtpu_get_missing_clock.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_get_missing_clock.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_history_bytes.restype = ctypes.c_int64
    lib.amtpu_history_bytes.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.amtpu_drop_doc.restype = ctypes.c_int64
    lib.amtpu_drop_doc.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.amtpu_get_clock.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_get_clock.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_get_missing_deps.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_get_missing_deps.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_get_missing_changes.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_get_missing_changes.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_buf_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    lib.amtpu_get_register.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_get_register.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_get_changes_for_actor.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_get_changes_for_actor.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_columnar_encode.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_columnar_encode.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_columnar_decode.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_columnar_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_begin_columnar.restype = ctypes.c_void_p
    lib.amtpu_begin_columnar.argtypes = [ctypes.c_void_p,
                                         ctypes.c_char_p, ctypes.c_int64]
    lib.amtpu_fold_settled.restype = ctypes.c_int64
    lib.amtpu_fold_settled.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int64]
    lib.amtpu_fold_clocks.restype = ctypes.c_int64
    lib.amtpu_fold_clocks.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int64, ctypes.c_int64]
    lib.amtpu_clock_pairs.restype = ctypes.c_int64
    lib.amtpu_clock_pairs.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.amtpu_op_count.restype = ctypes.c_int64
    lib.amtpu_op_count.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.amtpu_doc_ids.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_doc_ids.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_doc_stats.restype = ctypes.c_int64
    lib.amtpu_doc_stats.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_int64),
                                    ctypes.c_int64]
    lib.amtpu_doc_shard.restype = ctypes.c_uint32
    lib.amtpu_doc_shard.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                    ctypes.c_int]
    lib.amtpu_shard_split.restype = ctypes.c_void_p
    lib.amtpu_shard_split.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                      ctypes.c_int]
    lib.amtpu_shard_buf.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.amtpu_shard_buf.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                    ctypes.POINTER(ctypes.c_int64)]
    lib.amtpu_shard_free.argtypes = [ctypes.c_void_p]
    lib.amtpu_scan_changes.restype = None
    lib.amtpu_scan_changes.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
    return lib


_lib = None


def lib():
    global _lib
    if _lib is None:
        _lib = _load()
    return _lib


def _np_view(ptr, shape, dtype):
    n = int(np.prod(shape))
    if n == 0:
        return np.zeros(shape, dtype)
    arr = np.ctypeslib.as_array(ptr, shape=(n,))
    return arr.reshape(shape).view(dtype) if arr.dtype != dtype else \
        arr.reshape(shape)


def _take_buf(ptr, length):
    try:
        return ctypes.string_at(ptr, length)
    finally:
        lib().amtpu_buf_free(ptr)


# ---------------------------------------------------------------------------
# native columnar codec bindings (ISSUE 14; storage/columnar.py
# dispatches here under AMTPU_STORAGE_NATIVE)
# ---------------------------------------------------------------------------


def columnar_encode_native(raws):
    """C++ columnar encode: list of raw change bytes ->
    (blob, n_changes, n_residual).  Blob bytes are identical to the
    Python encoder's (the fuzz parity lane pins it).  Raws cross the
    boundary BIN-wrapped -- element boundaries must be explicit, a
    residual raw with trailing bytes is not re-delimitable by msgpack
    skip.  Raises on any native error; the columnar.py dispatch falls
    back to the Python codec then."""
    payload = msgpack.packb([bytes(r) for r in raws],
                            use_bin_type=True)
    out_len = ctypes.c_int64()
    stats = (ctypes.c_int64 * 2)()
    ptr = lib().amtpu_columnar_encode(payload, len(payload),
                                      ctypes.byref(out_len), stats)
    if not ptr:
        _raise_last()
    return _take_buf(ptr, out_len.value), int(stats[0]), int(stats[1])


def scan_changes(buf, spans):
    """Op counts of the change arrays at `spans`, a list of (start,
    end) offsets into the bytes `buf`: -1 for a span that is not
    already in the form ``packb(unpackb(span))`` gives, or whose ops
    the count cannot read (core.cpp `amtpu_scan_changes`).  The C++
    walk runs without the interpreter lock."""
    pairs = np.ascontiguousarray(spans, dtype=np.int64).reshape(-1)
    ops = np.empty(len(spans), np.int64)
    lib().amtpu_scan_changes(
        buf, len(buf),
        pairs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(spans),
        ops.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return ops.tolist()


def columnar_decode_native(blob):
    """C++ columnar decode: blob -> list of raw change bytes, byte-
    identical to the encode input (BIN-wrapped across the boundary, as
    in `columnar_encode_native`).  Corruption raises ValueError
    (decode_columnar's contract; the C++ side reports it as kind 1)."""
    out_len = ctypes.c_int64()
    ptr = lib().amtpu_columnar_decode(blob, len(blob),
                                      ctypes.byref(out_len))
    if not ptr:
        raise ValueError('corrupt columnar blob: %s'
                         % lib().amtpu_last_error().decode())
    return msgpack.unpackb(_take_buf(ptr, out_len.value), raw=False)


# ---------------------------------------------------------------------------
# batch-handle accounting: every amtpu_begin* success increments, every
# free decrements -- the assertion hook tests use to prove a phase-a
# failure cannot leak the C++ batch handle (each handle owns the whole
# decoded batch, so a leak under sustained error traffic is unbounded
# memory growth).
# ---------------------------------------------------------------------------

_live_lock = threading.Lock()
_live_batches = 0


def _track_begin():
    global _live_batches
    with _live_lock:
        _live_batches += 1


def _free_batch(bh):
    """The ONLY way batch handles are freed: pairs the counter with the
    C++ free so live_batch_handles() stays truthful."""
    global _live_batches
    lib().amtpu_batch_free(bh)
    with _live_lock:
        _live_batches -= 1


def live_batch_handles():
    """Currently allocated C++ batch handles (test/leak-audit hook)."""
    with _live_lock:
        return _live_batches


def _rollback_batch(bh, exc=None):
    """Best-effort pool rollback of a FAILED batch (pre-free).

    Success (returns True) means the pool is byte-identical to its
    pre-begin state: the failure is retryable/bisectable because re-
    applying the same changes is not swallowed by seq dedup.  Failure
    means emit already ran; the exception is marked
    ``amtpu_state_suspect`` so `resilience` refuses to re-apply those
    docs (the pre-resilience whole-batch raise is the only safe
    outcome there).
    """
    if lib().amtpu_batch_rollback(bh) != 0:
        if exc is not None:
            exc.amtpu_state_suspect = True
        telemetry.metric('resilience.rollback_unavailable')
        recorder.record('batch.rollback', detail='state_suspect')
        return False
    telemetry.metric('resilience.rollback')
    recorder.record('batch.rollback',
                    detail=type(exc).__name__ if exc is not None
                    else None)
    return True


def _batch_docs(bh, payload):
    """Doc keys of a begun batch -- fault-pinning lookups only (the
    disarmed fast path never calls this)."""
    if isinstance(payload, tuple):
        head = ctypes.string_at(payload[0], min(payload[1], 16))
    else:
        head = bytes(payload[:16])
    n = _read_map_header(head)[0]
    L = lib()
    return [L.amtpu_batch_doc_id(bh, i).decode() for i in range(n)]


def _packed_epilogue_on():
    """AMTPU_PACKED_EPILOGUE=0 forces the full-matrix member epilogue
    (the pre-packed readback path, kept as the parity A/B arm); default
    on.  Checked per batch, not latched."""
    return env_bool('AMTPU_PACKED_EPILOGUE', True)


def _conf_dense_thresh():
    """Dense-conflicts switch factor: the row-gather kernel saves
    nothing once `conf_rows * thresh > Tp` -- transfer the whole matrix
    and slice host-side instead.  AMTPU_CONF_DENSE_THRESH overrides the
    default factor 4 (0 disables the dense path entirely)."""
    return env_int('AMTPU_CONF_DENSE_THRESH', 4)


def _ctx_ready(ctx):
    """True when every device output phase b will block on has already
    resolved -- the ready-order collect predicate.  Host-only modes
    (hostreg) are always ready."""
    for arr in _ctx_pending_arrays(ctx):
        is_ready = getattr(arr, 'is_ready', None)
        if is_ready is not None and not is_ready():
            return False
    return True


def _ctx_pending_arrays(ctx):
    out = []
    combo = ctx.get('combo')
    if combo is not None:
        out.append(combo)
    else:
        if ctx.get('reg_out') is not None:
            out.append(ctx['reg_out']['packed'])
        if ctx.get('rank') is not None:
            # the non-fused path's list rank: phase a only started its
            # transfer (`_run_resolver`), phase b reads it
            out.append(ctx['rank'])
    esc = ctx.get('esc')
    if esc:
        out.extend(t_out['packed'] for _w, _rows, t_out in esc[0])
    return out


def _unwind_batch(ctx, exc):
    """Failure protocol of a begun batch, before its handle is freed:
    drain every device output still in flight, then roll the pool
    back.  The drain comes first: those dispatches zero-copied the C++
    batch columns the free deletes, and would read freed memory; their
    own errors are moot, the batch is already failing."""
    for arr in _ctx_pending_arrays(ctx):
        try:
            arr.block_until_ready()
        except Exception:
            pass
    _rollback_batch(ctx['bh'], exc)


def _run_phase_b_entry(key, pool, ctx, on_result=None, on_error=None):
    """Phase b of ONE (key, pool, ctx) entry, with the full failure
    protocol: drain in-flight kernels, roll the batch back, free the
    handle.  Shared by the serial ready-order collector below and the
    mesh pool's threaded collector (mesh_pool._collect_ready_parallel),
    so the two drivers cannot drift on error semantics."""
    try:
        result = pool._phase_b(ctx)
        if on_result is not None:
            on_result(key, result)
    except Exception as e:
        # a phase-b failure (armed fault, device error) can leave
        # dispatches in flight
        _unwind_batch(ctx, e)
        if on_error is not None:
            on_error(key, e)
        else:
            raise
    finally:
        _free_batch(ctx['bh'])


def _collect_ready_order(entries, on_result=None, on_error=None):
    """Drives phase b over (key, pool, ctx) entries READY-FIRST: each
    round picks the first entry whose dispatched device outputs have
    already resolved (jax.Array.is_ready) and runs its host mid/emit;
    only when nothing is ready does it block on the oldest submission.
    One slow shard then no longer stalls shards whose results are
    already sitting in host memory -- shard k's C++ mid/emit overlaps
    shard k+1's in-flight device wait (ISSUE 3 tentpole b).

    Every entry runs to completion regardless of earlier failures (their
    begins have committed state); errors go to `on_error(key, exc)`."""
    pending = list(entries)
    while pending:
        pick = None
        for i, (_key, _pool, ctx) in enumerate(pending):
            if _ctx_ready(ctx):
                pick = i
                break
        if pick is None:
            # nothing resolved yet: block on the oldest submission
            pick = 0
            trace.metric('collect.wait_in_order')
        elif pick > 0:
            trace.metric('collect.ready_reorder')
        key, pool, ctx = pending.pop(pick)
        _run_phase_b_entry(key, pool, ctx, on_result, on_error)


def apply_payloads_pipelined(pools_payloads):
    """Applies (NativeDocPool, payload_bytes) pairs with host/device
    overlap: every pool's begin + kernel dispatch runs first (phase a),
    then results collect and emit ready-first (phase b) -- pool k's
    device work overlaps pool k+1's host begin AND pool j's mid/emit,
    the same pattern ShardedNativePool uses across shards.  The PUBLIC
    entry for fanning a round of independent deliveries (replica
    catch-up) over many pools.

    Pools that already began successfully still run to completion when a
    later one fails; the first error is re-raised afterwards."""
    ctxs = []
    errors = []
    for pool, payload in pools_payloads:
        try:
            # overlapped: callers may pass the same pool more than once,
            # so a later begin must not donate a table an earlier
            # in-flight dispatch still reads
            ctxs.append((None, pool, pool._phase_a(payload,
                                                   overlapped=True)))
        except Exception as e:
            errors.append(e)
    _collect_ready_order(ctxs,
                         on_error=lambda _k, e: errors.append(e))
    if errors:
        raise errors[0]


#: fixed byte prefix of a v1 checkpoint; the remainder is the raw
#: changes array (the v2 columnar container lives in
#: automerge_tpu.storage -- this alias keeps the byte-splice loader
#: self-contained)
_CKPT_PREFIX = (b'\x82' + msgpack.packb('format') +
                msgpack.packb('amtpu-doc-v1') + msgpack.packb('changes'))


def _base_pool_of(pool, doc_id):
    """The NativeDocPool that actually owns `doc_id`'s state: sharded /
    mesh pools route per doc; a plain pool is its own base."""
    if hasattr(pool, '_shard_of'):
        return pool.pools[pool._shard_of(doc_id)]
    return pool


def _v2_adopt_info(pool, doc_id, key, adopts, frontier, chunks,
                   empty_pools):
    """Queues the post-apply snapshot re-adopt for a v2 container --
    ONLY into docs that are empty pre-load (see _load_batch's inline
    rationale: adopting over a live doc would discard newer compacted
    chunks).  `empty_pools` caches base-pool emptiness: a cold restart
    into a fresh pool (the 1M-doc case) skips the per-doc clock query
    entirely."""
    from .. import storage
    if frontier and chunks and storage.storage_format() != 'json':
        bp = _base_pool_of(pool, doc_id)
        empty = empty_pools.get(id(bp))
        if empty is None:
            empty = empty_pools[id(bp)] = bp.doc_count() == 0
        if not empty:
            pre = {}
            try:
                pre = pool.get_clock(doc_id).get('clock') or {}
            except Exception:
                pass
            if pre:
                return
        adopts.append((doc_id, key, frontier, chunks))


def _load_batch_native(pool, blobs):
    """Arena-direct restore (ISSUE 14 tentpole): v2 snapshot chunks +
    tail ship to C++ AS COLUMNAR BLOBS (`amtpu_begin_columnar`) -- the
    columns materialize straight into ChangeRec arena state with no
    Python change dicts and no per-change msgpack round trip; v1
    containers splice their raw changes array through the same entry.
    Docs group per base pool, so sharded/mesh drivers route exactly
    like the dict path.  Byte parity with the dict-replay path is the
    decode-parity test lane's contract (both exec modes)."""
    from .. import storage
    from ..errors import RangeError
    groups = {}          # id(base pool) -> (base pool, {key: [parts]})
    adopts = []          # (doc_id, key, frontier, chunks) post-apply
    empty_pools = {}     # id(base pool) -> was empty pre-load
    for doc_id, data in blobs.items():
        key = doc_key(doc_id)
        data = bytes(data)
        if data.startswith(_CKPT_PREFIX):
            doc_parts = [data[len(_CKPT_PREFIX):]]
        elif data.startswith(storage.CKPT_V2_PREFIX):
            try:
                frontier, chunks, tail_blob = \
                    storage.unpack_checkpoint_parts(data)
            except ValueError as e:
                raise RangeError('corrupt checkpoint for %r: %s'
                                 % (doc_id, e))
            doc_parts = list(chunks) + [tail_blob]
            _v2_adopt_info(pool, doc_id, key, adopts, frontier,
                           chunks, empty_pools)
        else:
            raise RangeError('not an amtpu-doc checkpoint: %r'
                             % (doc_id,))
        bp = _base_pool_of(pool, doc_id)
        groups.setdefault(id(bp), (bp, {}))[1][key] = doc_parts
    def apply_group(bp, keyed):
        try:
            bp._apply_columnar(msgpack.packb(keyed, use_bin_type=True))
        except RangeError as e:
            # corrupt-blob surface parity with the dict-replay arm: a
            # bad chunk/tail reports as a corrupt CHECKPOINT
            raise RangeError('corrupt checkpoint (docs %s): %s'
                             % (sorted(keyed), e))

    if len(groups) > 1:
        # sharded/mesh pools: drive the per-shard restores CONCURRENTLY
        # (ctypes releases the GIL around the C++ begin/emit), matching
        # the dict-replay arm's threaded shard runner.  Shards commit
        # independently -- the first error re-raises after every group
        # ran, the documented sharded-pool error contract.
        import concurrent.futures
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=min(len(groups), os.cpu_count() or 1)) \
                as pool_exec:
            futs = [pool_exec.submit(apply_group, bp, keyed)
                    for bp, keyed in groups.values()]
            errors = [f.exception() for f in futs
                      if f.exception() is not None]
        if errors:
            raise errors[0]
    else:
        for bp, keyed in groups.values():
            apply_group(bp, keyed)
    for doc_id, key, frontier, chunks in adopts:
        _base_pool_of(pool, doc_id)._adopt_snapshot(key, frontier,
                                                    chunks)


def _load_batch(pool, blobs):
    """Splices many save() checkpoints into ONE {doc: [changes]} payload
    and applies it as a single batch -- per-doc loads each pay a full
    device round trip; a whole DocSet restore should pay one.  v2
    columnar containers (docs/STORAGE.md) decode their snapshot chunks
    here and, post-apply, re-adopt them so a reloaded doc keeps its
    compacted cold-state economics.

    Under ``AMTPU_STORAGE_NATIVE`` (default on) the restore goes
    ARENA-DIRECT through `amtpu_begin_columnar` instead
    (`_load_batch_native`); this dict-replay body is the =0 parity
    oracle."""
    from .. import storage
    from ..errors import RangeError
    if faults.ARMED:
        faults.fire('checkpoint.load', [doc_key(d) for d in blobs])
    if storage.storage_native_on():
        return _load_batch_native(pool, blobs)
    parts = [_map_header(len(blobs))]
    adopts = []          # (doc_id, key, frontier, chunks) post-apply
    for doc_id, data in blobs.items():
        key = doc_key(doc_id)
        if data.startswith(_CKPT_PREFIX):
            parts.append(msgpack.packb(key, use_bin_type=True))
            parts.append(memoryview(data)[len(_CKPT_PREFIX):])
            continue
        if not data.startswith(storage.CKPT_V2_PREFIX):
            raise RangeError('not an amtpu-doc checkpoint: %r'
                             % (doc_id,))
        try:
            frontier, chunks, tail = \
                storage.unpack_checkpoint(bytes(data))
            raws = []
            for chunk in chunks:
                raws.extend(storage.decode_columnar(chunk))
        except ValueError as e:
            # the RangeError contract covers corrupt containers too --
            # whatever the columnar decoder tripped on internally
            raise RangeError('corrupt checkpoint for %r: %s'
                             % (doc_id, e))
        raws.extend(tail)
        parts.append(msgpack.packb(key, use_bin_type=True))
        parts.append(storage.join_changes_array(raws))
        if frontier and chunks and storage.storage_format() != 'json':
            # adopt ONLY into docs that are empty pre-load: loading an
            # (older) checkpoint into a LIVE doc replays as seq-deduped
            # no-ops, and overwriting that doc's storage state with the
            # checkpoint's would discard newer compacted chunks (changes
            # then live in neither arena nor snapshot) -- and the
            # checkpoint's application-order prefix need not be a
            # prefix of the live doc's.  A live target just stays on
            # its own (possibly uncompacted) state.
            pre = {}
            try:
                pre = pool.get_clock(doc_id).get('clock') or {}
            except Exception:
                pass
            if not pre:
                adopts.append((doc_id, key, frontier, chunks))
    pool.apply_batch_bytes(b''.join(parts))
    for doc_id, key, frontier, chunks in adopts:
        _base_pool_of(pool, doc_id)._adopt_snapshot(key, frontier,
                                                    chunks)


def _restore_threads():
    """``AMTPU_RESTORE_THREADS``: restore fan-out width (0 = auto, one
    worker per core capped at 8; 1 = serial -- the A/B arm the
    coldstart gate compares against)."""
    n = env_int('AMTPU_RESTORE_THREADS', 0)
    if n <= 0:
        n = min(8, os.cpu_count() or 1)
    return n


def restore_from_store(pool, store, doc_ids=None, batch=None,
                       threads=None):
    """Parallel arena-direct restore straight off a ColdStore's durable
    manifest (ISSUE 17 tentpole): walks the store's doc inventory, reads
    + checksums blobs, and fans per-shard doc groups across a thread
    pool where each shard runs its own `amtpu_begin_columnar` decode +
    apply with the GIL released -- the 1M-doc cold-start entry point.

    * **Sharding.** Docs group by base pool (`_base_pool_of`); each
      group restores on its own worker, serially batched
      (``AMTPU_RESTORE_BATCH``, default 8192 docs) -- a single
      NativeDocPool applies single-threaded by contract, so the
      parallel axis is the shard, exactly like the dict-replay arm's
      threaded shard runner.  Within a group, the next batch's blob
      reads prefetch on a side thread while the current batch applies
      (I/O overlaps decode even at one shard).
    * **Failure isolation.** A corrupt blob (checksum mismatch --
      `ColdStoreCorrupt`) quarantines THAT doc: typed per-doc error in
      the summary + ``storage.restore.corrupt``, never a whole-restore
      failure.  A failed batch apply falls back to per-doc application
      (the `DocEvictor.ensure_resident` pattern); docs that still fail
      land in the summary as resilience error envelopes +
      ``storage.restore.failed``.
    * **Progress.** ``storage.restore.{docs,bytes,batches}`` advance
      per applied batch (scrapable mid-restore) and the flight recorder
      logs start/finish + every quarantined doc.

    Returns a summary dict: ``{'docs', 'bytes', 'batches', 'corrupt':
    {doc: error}, 'failed': {doc: error}, 'elapsed_s'}``.
    """
    from ..storage.coldstore import ColdStoreCorrupt
    from .. import resilience
    t0 = time.perf_counter()
    if doc_ids is None:
        doc_ids = sorted(store.doc_ids())
    else:
        doc_ids = list(doc_ids)
    if batch is None:
        batch = max(1, env_int('AMTPU_RESTORE_BATCH', 8192))
    if threads is None:
        threads = _restore_threads()
    recorder.record('restore.start', n=len(doc_ids),
                    detail='threads=%d batch=%d' % (threads, batch))
    groups = {}          # id(base pool) -> (base pool, [doc ids])
    if hasattr(pool, '_shard_of'):
        pool.pools     # materialize the lazy shard list on THIS thread
    for d in doc_ids:
        bp = _base_pool_of(pool, d)
        groups.setdefault(id(bp), (bp, []))[1].append(d)
    lock = threading.Lock()
    summary = {'docs': 0, 'bytes': 0, 'batches': 0,
               'corrupt': {}, 'failed': {}}

    def read_blobs(ids):
        """One batch's blobs off the store, checksums verified; corrupt
        docs quarantine here (typed, counted, skipped)."""
        blobs = {}
        for d in ids:
            try:
                blobs[d] = store.get(d)
            except ColdStoreCorrupt as e:
                telemetry.metric('storage.restore.corrupt')
                recorder.record('restore.corrupt', doc=doc_key(d),
                                detail=str(e))
                with lock:
                    summary['corrupt'][d] = resilience.error_envelope(e)
            except KeyError:
                pass   # dropped between inventory walk and read
        return blobs

    def apply_blobs(bp, blobs):
        if not blobs:
            return
        try:
            _load_batch(bp, blobs)
        except Exception as batch_exc:
            # per-doc isolation (the ensure_resident pattern): one
            # poison blob must not fail the other docs of its batch
            for d, data in blobs.items():
                try:
                    _load_batch(bp, {d: data})
                except Exception as e:
                    telemetry.metric('storage.restore.failed')
                    recorder.record('restore.failed', doc=doc_key(d),
                                    detail=str(e))
                    with lock:
                        summary['failed'][d] = \
                            resilience.error_envelope(e)
            del batch_exc
        with lock:
            summary['docs'] += len(blobs)
            summary['bytes'] += sum(len(v) for v in blobs.values())
            summary['batches'] += 1
        telemetry.metric('storage.restore.docs', len(blobs))
        telemetry.metric('storage.restore.bytes',
                         sum(len(v) for v in blobs.values()))
        telemetry.metric('storage.restore.batches')

    def run_group(bp, ids):
        import concurrent.futures
        chunks = [ids[i:i + batch] for i in range(0, len(ids), batch)]
        # single-reader prefetch: batch k+1's store reads overlap batch
        # k's decode+apply (reads release the GIL around file I/O)
        with concurrent.futures.ThreadPoolExecutor(1) as reader:
            pending = reader.submit(read_blobs, chunks[0]) \
                if chunks else None
            for i in range(len(chunks)):
                blobs = pending.result()
                pending = reader.submit(read_blobs, chunks[i + 1]) \
                    if i + 1 < len(chunks) else None
                apply_blobs(bp, blobs)

    group_list = [g for g in groups.values() if g[1]]
    if len(group_list) > 1 and threads > 1:
        import concurrent.futures
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=min(threads, len(group_list))) as ex:
            futs = [ex.submit(run_group, bp, ids)
                    for bp, ids in group_list]
            errors = [f.exception() for f in futs
                      if f.exception() is not None]
        if errors:
            raise errors[0]
    else:
        for bp, ids in group_list:
            run_group(bp, ids)
    summary['elapsed_s'] = round(time.perf_counter() - t0, 3)
    recorder.record('restore.done', n=summary['docs'],
                    detail='%.3fs corrupt=%d failed=%d'
                           % (summary['elapsed_s'],
                              len(summary['corrupt']),
                              len(summary['failed'])))
    return summary


def _apply_batch_dicts(pool, changes_by_doc):
    """Shared dict-level apply_batch: msgpack round trip through the
    pool's RESILIENT wire path (pool is any object with
    apply_batch_bytes_resilient) -- a device/native-path failure is
    retried, bisected, and at worst quarantined per doc instead of
    failing every doc in the batch (automerge_tpu.resilience).

    Returns ``{doc_id: patch}`` in request order.  Inside
    `patch_map.byte_results()` (the gateway's flush) that is a
    `PatchMap` over the pool's result bytes, which decodes a doc only
    when something reads it; every other caller gets decoded dicts.
    A mapping with a ``packed()`` method (the gateway's merged requests,
    `utils.request_map.BatchDocs`) hands over its own payload and op
    count, its frames' change bytes spliced in.  The `pool.repack` span
    covers the payload before the pool call and, after it, either the
    walk that finds each doc's span or the whole `unpackb`, plus the
    `OPS` count."""
    with telemetry.span('pool.repack'):
        packed = getattr(changes_by_doc, 'packed', None)
        if packed is not None:
            payload, n_ops = packed()
        else:
            keyed = {NativeDocPool._doc_key(d): chs
                     for d, chs in changes_by_doc.items()}
            payload = msgpack.packb(keyed, use_bin_type=True)
            n_ops = None
    raw = pool.apply_batch_bytes_resilient(payload)
    with telemetry.span('pool.repack'):
        if patch_map.wanted():
            out = patch_map.PatchMap(raw, changes_by_doc)
        else:
            out = msgpack.unpackb(raw, raw=False, strict_map_key=False)
            out = {d: out[NativeDocPool._doc_key(d)]
                   for d in changes_by_doc}
        # AFTER the apply so a failed batch doesn't inflate it; counts
        # submitted ops of committed batches -- duplicates/queued
        # changes included (the engine path counts exact causally-
        # applied ops).  A packed batch counted its spliced docs' ops
        # in the reader's native scan, so it decodes nothing here
        if n_ops is None:
            n_ops = sum(len(c.get('ops', ()))
                        for chs in changes_by_doc.values() for c in chs)
        telemetry.OPS.inc(n_ops)
        return out


def _raise_if_quarantined(doc_id, result):
    """Single-doc entry points keep their raise contract: a one-doc
    batch has nothing to isolate FROM, so a quarantine envelope there
    surfaces as the exception it stands for.  The message embeds
    ``resilience.QUARANTINE_RAISE_MARKER`` -- the gateway's fan-out
    recognizes this surface to keep its 'envelope, not silence'
    promise to subscribers."""
    from ..resilience import QUARANTINE_RAISE_MARKER, is_quarantined
    if is_quarantined(result):
        from ..errors import AutomergeError
        raise AutomergeError('doc %r%s%s] %s'
                             % (doc_id, QUARANTINE_RAISE_MARKER,
                                result['errorType'], result['error']))


def _raise_last():
    from ..errors import AutomergeError, RangeError
    msg = lib().amtpu_last_error().decode()
    kind = lib().amtpu_last_error_kind()
    if kind == 2:
        raise TypeError(msg)
    raise (RangeError if kind == 1 else AutomergeError)(msg)


def _pipeline_depth():
    """Cross-batch staging depth of the double-buffered wave pipeline
    (AMTPU_PIPELINE_DEPTH, default 2; 0/1 disables).  Each wave is a
    doc-disjoint slice of the payload begun while earlier waves' device
    kernels are still in flight -- wave k+1's C++ decode/begin (GIL
    released) overlaps wave k's XLA compute, the cross-BATCH extension
    of the cross-shard overlap `_collect_ready_order` already drives."""
    return env_int('AMTPU_PIPELINE_DEPTH', 2)


def _pipeline_min_docs():
    """Smallest doc count worth splitting into waves: below this the
    per-wave fixed cost (split pass, extra dispatch, jit shape) beats
    the overlap.  AMTPU_PIPELINE_MIN_DOCS overrides (default 64)."""
    return env_int('AMTPU_PIPELINE_MIN_DOCS', 64)


def _host_dom_on():
    """Host-Fenwick dominance instead of the device kernel.

    The [L]x[L,K] dominance mask products are the right formulation on
    an accelerator (MXU work, stays fused with resolve+linearize) but
    O(T*L) scalar work on the CPU backend, where they dominate
    single-big-doc latency.  Default: host path on CPU, device path on
    accelerators; AMTPU_HOST_DOM=1/0 forces either way (checked per
    batch, not latched)."""
    env = env_raw('AMTPU_HOST_DOM')
    if env is not None:
        return env not in ('', '0')
    import jax
    return jax.default_backend() == 'cpu'


#: resident-mode knobs that BIND at a process's first batch: C++ static
#: latches (core.cpp resident_enabled_pre / resclk_enabled) + jit cache
#: shapes.  AMTPU_HOST_FULL is deliberately absent -- it is re-read per
#: batch (the exec-mode A/B tests flip it in-process).
# AMTPU_MESH is latched like the resident knobs: the pool factory's
# choice and each chip's device binding are fixed at construction, so a
# later env flip must warn, not silently serve the old topology.  (The
# sp-fence threshold AMTPU_MESH_SP_MIN is deliberately NOT here -- the
# fence reads it live per dispatch, so flips genuinely apply.)
_RESIDENT_LATCH_KEYS = ('AMTPU_RESIDENT', 'AMTPU_RESIDENT_MIN',
                        'AMTPU_RESIDENT_CLK', 'AMTPU_RESCLK_MAX_ACTORS',
                        'AMTPU_RESCLK_MAX_ROWS', 'AMTPU_TRIVIAL_HOST',
                        'AMTPU_MESH')
# flips of the mesh-topology knob count under mesh.*; everything else
# stays resident.latch_flip_ignored
_LATCH_COUNTER_NS = {'AMTPU_MESH': 'mesh'}
_resident_latch = None          # first-batch snapshot
_latch_flips_warned = set()     # (key, new value) pairs already warned


def _atoi(s):
    """C atoi: leading integer or 0 -- the parse the C++ latches use."""
    m = re.match(r'\s*[-+]?\d+', s or '')
    return int(m.group()) if m else 0


_latch_defaults_cache = None


def _latch_defaults():
    """(resident_min, resclk_max_actors, resclk_max_rows) defaults read
    through the ABI (amtpu_latch_defaults): the flip guard's effective
    values can never drift from the constants core.cpp latches on."""
    global _latch_defaults_cache
    if _latch_defaults_cache is None:
        out = (ctypes.c_int64 * 3)()
        lib().amtpu_latch_defaults(out)
        _latch_defaults_cache = tuple(int(v) for v in out)
    return _latch_defaults_cache


def _latch_snapshot():
    """(raw, effective) views of the latch knobs.  Effective values
    mirror each knob's actual consumers, so a semantically no-op env
    change (e.g. exporting a numeric knob's default) does not warn:

    * AMTPU_RESIDENT stays raw -- the Python arena/dominance gates
      distinguish unset (backend-dependent) from any set value;
    * AMTPU_RESIDENT_CLK's only consumer is core.cpp's resclk_enabled:
      atoi(CLK, falling back to RESIDENT) != 0, default on;
    * the numeric knobs compare as parsed integers with the C++
      defaults filled in;
    * AMTPU_TRIVIAL_HOST mirrors core.cpp's trivial_host static:
      atoi != 0, default on;
    * AMTPU_MESH compares as the normalized (dp, sp) the pool factory
      parses (malformed values compare raw -- they never built a
      mesh)."""
    raw = tuple(env_raw(k) for k in _RESIDENT_LATCH_KEYS)
    res, rmin, clk, amax, arows, triv, mesh = raw
    clk_src = clk if clk is not None else res
    d_rmin, d_amax, d_arows = _latch_defaults()
    try:
        mesh_eff = parse_mesh_env()
    except ValueError:
        mesh_eff = mesh
    eff = (res,
           _atoi(rmin) if rmin is not None else d_rmin,
           True if clk_src is None else _atoi(clk_src) != 0,
           _atoi(amax) if amax is not None else d_amax,
           _atoi(arows) if arows is not None else d_arows,
           True if triv is None else _atoi(triv) != 0,
           mesh_eff)
    return raw, eff


def _check_resident_latch():
    """Enforce the latch-at-first-batch contract instead of silently
    ignoring flips (ISSUE 6): the first batch snapshots the
    AMTPU_RESIDENT* knobs; a later divergence warns once per (key,
    value) and counts ``resident.latch_flip_ignored``.  The flipped env
    stays ignored exactly as before -- the C++ statics latched and the
    jit caches already compiled against the first-batch values; only a
    process restart can apply it (bench.py's subprocess-per-config
    protocol exists for this reason)."""
    global _resident_latch
    cur = _latch_snapshot()
    if _resident_latch is None:
        _resident_latch = cur
        return
    if cur[1] == _resident_latch[1]:    # effective values decide
        return
    import warnings
    for key, was, now, was_eff, now_eff in zip(
            _RESIDENT_LATCH_KEYS, _resident_latch[0], cur[0],
            _resident_latch[1], cur[1]):
        if was_eff == now_eff:
            continue
        trace.metric('%s.latch_flip_ignored'
                     % _LATCH_COUNTER_NS.get(key, 'resident'))
        if (key, now) not in _latch_flips_warned:
            _latch_flips_warned.add((key, now))
            warnings.warn(
                '%s changed %r -> %r after the first batch; resident-'
                'mode knobs latch at first use, so the flip is IGNORED '
                '(restart the process to apply it)' % (key, was, now),
                RuntimeWarning, stacklevel=3)


def _host_full_on():
    """Full host path: no kernel dispatch at all -- C++ resolves
    registers in-emit and list indexes via an in-emit Fenwick sweep.

    The right default on the CPU backend, where the XLA kernels share
    the single host core the C++ engine runs on and every dispatch is
    pure overhead.  Accelerators keep the kernel path (that is the
    point of the framework); a forced AMTPU_RESIDENT=1 also keeps it,
    so the resident tests and the multichip dryrun still drive the
    device-resident dispatch on CPU.  AMTPU_HOST_FULL=1/0 forces."""
    env = env_raw('AMTPU_HOST_FULL')
    if env is not None:
        return env not in ('', '0')
    # any truthy AMTPU_RESIDENT forces the resident kernel path -- same
    # parse as the C++ gate (atoi != 0), not just the literal '1'
    res = env_raw('AMTPU_RESIDENT')
    if res is not None and res not in ('', '0'):
        return False
    import jax
    return jax.default_backend() == 'cpu'


def _raise_shard_errors(errors):
    """Per-shard error reporting: a single failure re-raises with its
    shard identified; multiple failures aggregate every shard's message
    so no diagnosis is lost (healthy shards have already committed)."""
    if not errors:
        return
    if len(errors) == 1:
        shard, err = errors[0]
        err.args = ('[shard %d] %s' % (shard, err.args[0] if err.args
                                       else err),) + err.args[1:]
        raise err
    # aggregate, but keep the concrete exception class when every shard
    # failed the same way so callers' except clauses behave identically
    # whether one shard or all of them raised (e.g. all-ValueError must
    # surface as ValueError, same as the single-failure path above)
    from ..errors import AutomergeError
    types = {type(e) for _, e in errors}
    cls = types.pop() if len(types) == 1 else AutomergeError
    try:
        probe = cls('probe')          # must accept a lone message arg
    except Exception:
        cls, probe = AutomergeError, None
    if probe is not None and not isinstance(probe, Exception):
        cls = AutomergeError
    raise cls(
        '%d shards failed: ' % len(errors) +
        '; '.join('[shard %d] %s: %s' % (s, type(e).__name__, e)
                  for s, e in errors)) from errors[0][1]


class NativeDocPool:
    """C++ host runtime + JAX kernels: the batched resolver, patch-equal
    to the scalar oracle."""

    #: window width of the register kernel (ops/registers.WINDOW)
    WINDOW = 8
    #: entries amtpu_batch_dims writes -- must match core.cpp exactly
    #: (an undersized ctypes buffer is silent heap corruption)
    N_DIMS = 14

    def __init__(self):
        self._pool = lib().amtpu_pool_new()
        self._mode_set = False
        from .batch_resident import PoolClockCache
        from .resident import ResidentCache
        self._resident = ResidentCache()
        self._resclk = PoolClockCache()
        # per-doc settled-history snapshots (ISSUE 10, docs/STORAGE.md):
        # doc key -> {'frontier': {actor: seq}, 'chunks': [columnar
        # blob, ...]}.  The chunks hold exactly the changes <= frontier
        # in application order; the C++ arena holds only the tail.
        # Driven single-threaded under the callers' pool serialization
        # (the gateway's pool lock), like every other pool mutation.
        self._storage = {}

    @staticmethod
    def _backend_is_cpu():
        import jax
        return jax.default_backend() == 'cpu'

    def _ensure_mode_flags(self):
        # resolved lazily at the first batch (jax backend init is heavy
        # and pools are built in sharded bulk); re-checked never -- the
        # backend cannot change within a process
        if not self._mode_set:
            lib().amtpu_pool_set_hostfull(
                self._pool, 1 if _host_full_on() else 0)
            self._mode_set = True

    def __del__(self):
        # read the module global directly: at interpreter shutdown the
        # lib() accessor may already have been torn down
        if getattr(self, '_pool', None) and _lib is not None:
            _lib.amtpu_pool_free(self._pool)
            self._pool = None

    def doc_count(self):
        """Number of materialized docs (tests assert queries on unknown
        ids never create phantom state)."""
        return lib().amtpu_doc_count(self._pool)

    # -- wire path ------------------------------------------------------

    def apply_batch_bytes(self, payload):
        """msgpack {doc_id: [change...]} -> msgpack {doc_id: patch}."""
        t0 = time.perf_counter()
        if isinstance(payload, (bytes, bytearray)):
            try:
                docs = _read_map_header(payload)[0]
            except (ValueError, IndexError):
                # malformed header: skip pipelining and let C++ begin
                # raise its typed validation error (the resilience and
                # sidecar layers classify on that type)
                docs = 0
        else:
            # shard sub-call: never pipelined (the sharded driver
            # overlaps across shards itself) and the top level already
            # counted docs for telemetry -- no header parse needed
            docs = 0
        recorder.record('batch.begin', n=docs)
        if self._should_pipeline(payload, docs):
            try:
                out = self._apply_waves(payload, docs)
            except Exception as e:
                if getattr(e, 'amtpu_state_suspect', False):
                    raise
                # every begun wave rolled back pre-emit, so a serial
                # replay is safe -- and it restores the unpipelined
                # contract that a multi-error payload surfaces its
                # FIRST error in application order (C++ begin), which
                # wave hash-order begin would otherwise change with
                # AMTPU_PIPELINE_DEPTH
                trace.metric('pipeline.serial_replay')
                out = self._apply_unpipelined(payload)
        else:
            out = self._apply_unpipelined(payload)
        telemetry.observe_batch('native', time.perf_counter() - t0,
                                docs=docs)
        return out

    def _apply_unpipelined(self, payload):
        """One whole-payload phase a + b: the non-wave batch body.
        The always-on attribution seams split the wall at the phase
        boundary: `dispatch` = host begin + async device dispatch,
        `collect` = blocking on device outputs + host mid/emit."""
        t0 = time.perf_counter()
        ctx = self._phase_a(payload)
        t1 = time.perf_counter()
        attribution.note_flush_phase('dispatch', t1 - t0)
        try:
            return self._phase_b(ctx)
        except Exception as e:
            _unwind_batch(ctx, e)
            raise
        finally:
            attribution.note_flush_phase('collect',
                                         time.perf_counter() - t1)
            _free_batch(ctx['bh'])

    def _should_pipeline(self, payload, docs):
        """Wave pipelining engages only where the overlap is real and the
        semantics unchanged: enough docs to split, a device kernel to
        overlap (the full host path has no async device work -- C++
        begin and emit already saturate the core), no armed fault sites
        (chaos lanes pin exact single-batch rollback semantics), and not
        already inside a sharded driver's sub-call (tuple payloads),
        which pipelines across shards itself.  Both kernel layouts
        overlap: the fused path's outputs and the non-fused path's list
        rank are read in phase b, so no phase a blocks on its kernels."""
        if isinstance(payload, tuple):
            return False
        if docs < max(2, _pipeline_min_docs()) or _pipeline_depth() < 2:
            return False
        if faults.ARMED:
            return False
        self._ensure_mode_flags()
        return not _host_full_on()

    def _apply_waves(self, payload, docs):
        """Double-buffered cross-batch staging INSIDE one pool: the
        payload splits into doc-disjoint waves (the same FNV doc hash as
        the shard splitter), every wave's C++ begin + async kernel
        dispatch runs before any wave blocks on results, and phase b
        drains ready-first (`_collect_ready_order`) -- so wave k+1's
        decode/begin/encode overlaps wave k's in-flight device compute
        on the SAME NativeDocPool, on the fused and the non-fused
        (multi-size-class Text) layout alike: neither phase a reads a
        device output back.  Doc-disjointness is what makes the
        interleaved begins sound: the begin journal, register mirrors,
        member windows, and arenas are all doc-scoped, and the pool-
        global intern/clock tables are append-only.

        Failure semantics: any phase-a error rolls back every begun wave
        in reverse begin order -- nothing has emitted yet, so the call
        stays atomic exactly like the unpipelined path (validation/
        protocol errors all raise at begin).  A phase-b error
        (unreachable for well-formed pools; fault injection disables
        pipelining) rolls back the failed wave while healthy waves still
        run to completion -- the sharded driver's semantics -- and the
        re-raised exception is marked ``amtpu_state_suspect`` when any
        wave committed, so the resilience layer refuses a blind
        whole-payload re-apply instead of double-applying committed
        docs."""
        L = lib()
        depth = min(_pipeline_depth(), docs)
        # bytes only: _should_pipeline rejects shard sub-call views, and
        # waves must never nest inside a shard split (doc-disjointness
        # and failure semantics are reasoned per top-level payload)
        assert isinstance(payload, (bytes, bytearray))
        with trace.span('pipeline.split'):
            # the splitter copies doc sub-payloads into its own buffers,
            # so `payload` only needs to outlive this call
            sp = L.amtpu_shard_split(payload, len(payload), depth)
            if not sp:
                _raise_last()
        try:
            subs = []
            for s in range(depth):
                sub_len = ctypes.c_int64()
                ptr = L.amtpu_shard_buf(sp, s, ctypes.byref(sub_len))
                if sub_len.value > 1:
                    subs.append((ctypes.cast(ptr, ctypes.c_char_p),
                                 sub_len.value))
            ctxs = []
            t_loop0 = time.perf_counter()
            t_a0 = t_loop0
            try:
                for i, sub in enumerate(subs):
                    ctx = self._phase_a(sub, overlapped=True)
                    ctxs.append((i, self, ctx))
                    if i == 0:
                        t_a0 = time.perf_counter()
            except Exception as e:
                # atomic unwind: reverse begin order, nothing emitted;
                # each wave's in-flight kernels (its deferred rank
                # among them) drain before its batch is freed
                for _i, _p, ctx in reversed(ctxs):
                    _unwind_batch(ctx, e)
                    _free_batch(ctx['bh'])
                raise
            if len(ctxs) > 1:
                # host begin time of waves >0: the decode/begin work
                # that ran while wave 0's kernels were already in flight
                trace.metric('collect.overlap_s',
                             time.perf_counter() - t_a0)
            trace.metric('pipeline.batches')
            trace.metric('pipeline.waves', len(ctxs))
            t_disp = time.perf_counter()
            attribution.note_flush_phase('dispatch', t_disp - t_loop0)
            recorder.record('wave.dispatch', n=len(ctxs))
            results = [None] * len(ctxs)
            errors = []

            def keep(i, result):
                results[i] = result

            _collect_ready_order(
                ctxs, on_result=keep,
                on_error=lambda i, e: errors.append((i, e)))
            attribution.note_flush_phase('collect',
                                         time.perf_counter() - t_disp)
            recorder.record('wave.collect', n=len(ctxs))
            if errors:
                _i, err = errors[0]
                # suspect if any wave committed OR any other wave's
                # failure was itself marked suspect (post-emit rollback
                # failure): the marker must survive raising errors[0]
                if (any(r is not None for r in results)
                        or any(getattr(e, 'amtpu_state_suspect', False)
                               for _j, e in errors)):
                    err.amtpu_state_suspect = True
                raise err
            total = 0
            bodies = []
            for r in results:
                cnt, off = _read_map_header(r)
                total += cnt
                bodies.append(memoryview(r)[off:])
            return _map_header(total) + b''.join(bodies)
        finally:
            L.amtpu_shard_free(sp)

    def _phase_a(self, payload, overlapped=False):
        """Host begin + async device dispatch.  Returns a context dict;
        the caller MUST pass it to `_phase_b` and then free ctx['bh'].
        `overlapped=True` (the wave-pipelined driver) forbids donating
        the previous resident clock table: an earlier wave's in-flight
        kernels may still read it.

        `payload` is msgpack bytes, or a zero-copy (ctypes char pointer,
        length) pair -- the sharded driver passes views into the C++
        splitter's buffers; amtpu_begin copies what it keeps, so the
        buffer only needs to outlive this call.

        Splitting here lets a sharded driver overlap shard k+1's host
        `begin` with shard k's in-flight device work on a single thread
        (jax dispatches are async; the transfer is started with
        copy_to_host_async and collected in phase b)."""
        L = lib()
        if isinstance(payload, tuple):
            data, n = payload
        else:
            data, n = payload, len(payload)
        _check_resident_latch()
        self._ensure_mode_flags()
        with trace.span('host.begin'):
            bh = L.amtpu_begin(self._pool, data, n)
        if not bh:
            _raise_last()
        _track_begin()
        fault_docs = None
        if faults.ARMED:
            fault_docs = _batch_docs(bh, payload)
            try:
                faults.fire('native.begin', fault_docs)
            except Exception as e:
                # semantics: "begin failed" -- the pool must look
                # untouched, exactly like a real begin-phase throw
                _rollback_batch(bh, e)
                _free_batch(bh)
                raise
        return self._phase_a_rest(bh, fault_docs, overlapped=overlapped)

    def _phase_a_rest(self, bh, fault_docs=None, overlapped=False):
        """Post-begin half of phase a: read batch dims and dispatch the
        device kernels.  Shared by the batch and local-change entries."""
        L = lib()
        ctx = {'bh': bh, 'fault_docs': fault_docs}
        try:
            dims = (ctypes.c_int64 * self.N_DIMS)()
            L.amtpu_batch_dims(bh, dims)
            (T, Tp, A, Ap, Larena, Lp, n_blocks, max_obj, CTp,
             use_members, any_ovf, max_group, pre_ovf, host_full) = \
                [int(x) for x in dims]
            # 6 slots -- must match what amtpu_fused_dims writes exactly
            # (an undersized ctypes buffer is silent heap corruption)
            fdims = (ctypes.c_int64 * 6)()
            L.amtpu_fused_dims(bh, fdims)
            (fused_ok, W, dLp, dTp, resident_ok,
             res_clock) = [int(x) for x in fdims]
            trace.count('ops.register_rows', T)
            trace.count('ops.arena_elems', Larena)
            # member-window mode (hot keys): explicit candidate indexes +
            # host-computed overflow flags replace the sliding window
            mem = hovf = None
            if use_members and Tp > 0:
                mem = np.ctypeslib.as_array(L.amtpu_col_memidx(bh),
                                            shape=(Tp, self.WINDOW))
                hovf = np.ctypeslib.as_array(L.amtpu_col_hostovf(bh),
                                             shape=(Tp,))
            # Dynamic sliding-window width: the (W+1)^2 pairwise
            # intermediates of the register kernel dominate its cost,
            # and most batches never have more than 2-3 rows per
            # register (text: one set + maybe one delete per elemId).
            # A window covering the batch's widest group is EXACT --
            # saturation (the overflow->oracle fallback) needs a group
            # wider than the window, which cannot happen here.  Member
            # mode keeps the C++-built width.
            if use_members or max_group > self.WINDOW:
                weff = self.WINDOW
            else:
                weff = 2
                while weff < max_group:
                    weff *= 2
            wenv = env_raw('AMTPU_WEFF')
            if wenv and not use_members:
                # test-only: force a narrower window so the overflow
                # branch is REACHABLE (the dynamic sizing above makes
                # saturation impossible by construction); parity still
                # holds because flagged groups escalate through exact
                # wider kernel tiers (or the host oracle under
                # AMTPU_ESCALATE=0).  tests/test_native.py uses this to
                # pin the fallback paths under both dominance modes.
                weff = min(self.WINDOW, max(2, int(wenv)))
            ctx.update(dims=(T, Tp, A, Ap, Larena, Lp, n_blocks, max_obj,
                             CTp), mem=mem, hovf=hovf, weff=weff,
                       resident_ok=bool(resident_ok))

            if host_full:
                # full host path (CPU backend): C++ skipped the register
                # rows at begin; emit resolves registers + list indexes
                # itself (host_resolve_step + in-emit Fenwick)
                trace.count('hostfull.batches')
                trace.metric('hostfull.batches')
                ctx.update(mode='hostreg')
                return ctx

            # Host-register mode: when a map-only batch's register rows
            # mostly sit in groups wider than the member window, emit can
            # resolve each register against the live mirror in one O(w)
            # merge (no sort) with no dispatch at all.  That only beats
            # the kernel on the CPU backend, where XLA shares the host
            # core; on accelerators the escalation ladder keeps the
            # resolution on device (one wider dispatch per tier), so
            # hostreg engages only when the ladder is unavailable.  The
            # 64-writer replica catch-up shape (BASELINE config 5) is
            # the canonical CPU case.
            from ..ops.registers import escalation_enabled
            if (use_members and n_blocks == 0 and 2 * pre_ovf >= T
                    and env_bool('AMTPU_HOST_REG', True)
                    and (not escalation_enabled()
                         or self._backend_is_cpu())):
                trace.count('hostreg.batches')
                trace.metric('hostreg.batches')
                ctx.update(mode='hostreg')
                return ctx

            if res_clock and Tp > 0:
                # pool-resident clock table (tentpole a): sync the
                # device copy -- usually a delta upload of just this
                # batch's appended rows -- and stamp per-batch hit
                # accounting.  Computed only on the kernel paths (the
                # hostreg returns above never stage clocks).
                ctx['ctab_dev'] = self._resclk.table(
                    L, self._pool, donate_ok=not overlapped)
                stats = (ctypes.c_int64 * 2)()
                L.amtpu_resclk_batch_stats(bh, stats)
                if stats[0]:
                    trace.metric('resident.batch_hit_rows',
                                 int(stats[0]))
            elif not res_clock:
                # actor cap crossed mid-pool: release the (possibly
                # huge) device table the moment C++ disables the cache
                self._resclk.drop_if_disabled(L, self._pool)
            if faults.ARMED:
                faults.fire('device.dispatch', ctx['fault_docs'])
            if fused_ok:
                with trace.span('device.dispatch'):
                    self._dispatch_fused(L, ctx, Tp, Ap, CTp, Lp, max_obj,
                                         n_blocks, W, dLp, dTp)
            else:
                trace.count('fused.fallback_layout')
                trace.metric('fallback.layout_batches')
                with trace.span('device.dispatch'):
                    reg_out, rank = self._run_resolver(
                        L, bh, Tp, Ap, CTp, Lp, max_obj, mem,
                        weff=ctx['weff'],
                        ctab_dev=ctx.get('ctab_dev'))
                ctx.update(mode='old', reg_out=reg_out, rank=rank)
                # member-mode overflow flags are HOST-computed, so the
                # escalation tiers dispatch here -- async, overlapping
                # the pipeline's other host work -- and collect in
                # phase b (kernel-decided overflow, e.g. AMTPU_WEFF,
                # stays synchronous in _escalate)
                if hovf is not None and hovf.any():
                    from ..ops import registers as register_ops
                    if register_ops.escalation_enabled():
                        ctx['esc'] = self._escalation_dispatch(
                            L, ctx, hovf.astype(bool))
            return ctx
        except Exception as e:
            # phase-a failure frees its OWN handle (callers only see an
            # exception, never a ctx to free); the live-handle counter
            # stays balanced -- tests assert live_batch_handles() == 0
            # after forced phase-a errors.  Unwind first (drain what was
            # dispatched, roll back): begin already committed schedule
            # state, and a retry/bisect is only byte-safe against the
            # pre-begin pool.
            _unwind_batch(ctx, e)
            _free_batch(bh)
            raise

    def _register_views(self, L, bh, Tp, Ap, CTp, ctab_dev=None):
        """ctypes views of the register columns (single source of truth
        for their shapes/dtypes).  `ctab_dev` (the pool-resident device
        clock table) replaces the batch-local table view when the batch
        was encoded against pool-global clock rows (CTp == 0)."""
        if ctab_dev is not None:
            ctab = ctab_dev
        else:
            ctab = np.ctypeslib.as_array(L.amtpu_col_clocktab(bh),
                                         shape=(CTp, Ap))
        return dict(
            g=np.ctypeslib.as_array(L.amtpu_col_g(bh), shape=(Tp,)),
            t=np.ctypeslib.as_array(L.amtpu_col_t(bh), shape=(Tp,)),
            a=np.ctypeslib.as_array(L.amtpu_col_a(bh), shape=(Tp,)),
            s=np.ctypeslib.as_array(L.amtpu_col_s(bh), shape=(Tp,)),
            d=np.ctypeslib.as_array(L.amtpu_col_d(bh), shape=(Tp,)),
            ctab=ctab,
            cidx=np.ctypeslib.as_array(L.amtpu_col_clockidx(bh),
                                       shape=(Tp,)),
            si=np.ctypeslib.as_array(L.amtpu_col_sort(bh), shape=(Tp,)))

    def _arena_views(self, L, bh, Lp):
        """ctypes views of the arena columns."""
        return dict(
            obj=np.ctypeslib.as_array(L.amtpu_col_obj(bh), shape=(Lp,)),
            par=np.ctypeslib.as_array(L.amtpu_col_par(bh), shape=(Lp,)),
            ctr=np.ctypeslib.as_array(L.amtpu_col_ctr(bh), shape=(Lp,)),
            act=np.ctypeslib.as_array(L.amtpu_col_act(bh), shape=(Lp,)),
            val=np.ctypeslib.as_array(L.amtpu_col_val(bh), shape=(Lp,)),
            lsi=np.ctypeslib.as_array(L.amtpu_col_linsort(bh),
                                      shape=(Lp,)))

    def _dispatch_fused(self, L, ctx, Tp, Ap, CTp, Lp, max_obj, n_blocks,
                        W, dLp, dTp):
        from ..ops import list_rank, registers as register_ops
        bh = ctx['bh']
        if Tp == 0:
            # no register ops: nothing to resolve, and without list-assign
            # ops there are no dominance timelines either -- no dispatch
            ctx.update(mode='fused', combo=None, reg_out=None, rank=None)
            return
        r = self._register_views(L, bh, Tp, Ap, CTp,
                                 ctab_dev=ctx.get('ctab_dev'))
        mem = ctx.get('mem')

        def dispatch_registers_only(hostdom=False):
            # register resolution alone: either there is no list-assign
            # work at all (n_blocks == 0) or dominance indexes come from
            # the C++ Fenwick sweep (hostdom) -- rank is consumed by
            # nothing on the host in both cases
            if mem is not None:
                trace.count('ops.registers.members')
                reg_out = telemetry.h2d_call(
                    register_ops.resolve_registers_members,
                    r['t'], r['a'], r['s'], mem, r['d'].astype(bool),
                    r['ctab'], r['cidx'], window=ctx['weff'],
                    want_visible_before=False)
            else:
                # Pallas stencil kernel on TPU (VMEM-resident pairwise
                # temporaries), XLA twin elsewhere -- bit-equal outputs
                from ..ops.pallas_registers import resolve_registers_auto
                reg_out = telemetry.h2d_call(
                    resolve_registers_auto,
                    r['g'], r['t'], r['a'], r['s'], r['d'].astype(bool),
                    np.ones((Tp,), bool), r['si'], r['ctab'], r['cidx'],
                    window=ctx['weff'])
            combo = reg_out['packed']
            combo.copy_to_host_async()
            ctx.update(mode='fused', combo=combo, reg_out=reg_out,
                       rank=None, hostdom=hostdom)

        if n_blocks == 0:
            dispatch_registers_only()
            return
        if ctx.get('resident_ok') and mem is None and \
                self._dispatch_resident(L, ctx, Tp, Ap, CTp, max_obj,
                                        dLp, dTp):
            return
        if _host_dom_on():
            # CPU backend: dispatch ONLY register resolution; ranks and
            # dominance indexes come from the C++ Fenwick sweep in
            # phase b (amtpu_host_dominance) instead of the quadratic
            # device kernel.  See _host_dom_on for the rationale.
            dispatch_registers_only(hostdom=True)
            trace.count('hostdom.dispatch')
            return
        e = self._arena_views(L, bh, Lp)
        n_iters = list_rank.ceil_log2(max(max_obj, 1)) + 1
        v0 = np.ctypeslib.as_array(L.amtpu_dom_v0(bh, 0), shape=(W, dLp))
        er_src = np.ctypeslib.as_array(L.amtpu_fdom_ersrc(bh),
                                       shape=(W, dLp))
        oe = np.ctypeslib.as_array(L.amtpu_dom_oe(bh, 0), shape=(W, dTp))
        orank_src = np.ctypeslib.as_array(L.amtpu_fdom_oranksrc(bh),
                                          shape=(W, dTp))
        dom_src = np.ctypeslib.as_array(L.amtpu_fdom_domsrc(bh),
                                        shape=(W, dTp))
        ov = np.ctypeslib.as_array(L.amtpu_dom_ov(bh, 0), shape=(W, dTp))
        trace.count('ops.resolve_rank_dominate')
        trace.count('ops.registers.members' if mem is not None
                    else 'ops.registers.xla')
        reg_out, rank, combo = telemetry.h2d_call(
            register_ops.resolve_rank_dominate,
            r['g'], r['t'], r['a'], r['s'], r['ctab'], r['cidx'],
            r['d'].astype(bool), np.ones((Tp,), bool), r['si'],
            e['obj'], e['par'], e['ctr'], e['act'], e['val'].astype(bool),
            e['lsi'], n_iters,
            v0, er_src, oe, orank_src, dom_src, ov.astype(bool),
            window=ctx['weff'], mem_idx=mem)
        combo.copy_to_host_async()
        ctx.update(mode='fused', combo=combo, reg_out=reg_out, rank=rank)

    def _dispatch_resident(self, L, ctx, Tp, Ap, CTp, max_obj, dLp, dTp):
        """Fused dispatch over the DEVICE-RESIDENT arena (single big
        list object): uploads only per-batch deltas; the arena columns,
        visibility vector, and in-graph sibling sort live on device
        between batches (SURVEY hard part 5).  Returns False to fall
        back to the standard fused path (C++ refills the skipped
        layout arrays lazily)."""
        from ..ops import list_rank
        from .resident import _jit_kernel
        # Residency trades per-batch H2D of the whole arena for an
        # in-graph sibling sort: a clear win over a real device link,
        # a loss on the CPU backend where "transfers" are memcpys.
        # Default: on for accelerators, off for CPU; AMTPU_RESIDENT=1/0
        # overrides either way (C++ skips its O(arena) layout fills
        # optimistically and refills lazily when Python declines).
        env = env_raw('AMTPU_RESIDENT')
        if env is None:
            import jax
            if jax.default_backend() == 'cpu':
                return False
        bh = ctx['bh']
        meta = (ctypes.c_int64 * 4)()
        L.amtpu_dom_obj_meta(bh, 0, meta)
        doc_idx, obj_sid, base, n_now = [int(x) for x in meta]
        if base != 0 or n_now <= 0 or n_now > dLp:
            return False
        doc_id = L.amtpu_batch_doc_id(bh, doc_idx)
        entry = self._resident.get_entry(L, self._pool, doc_id, obj_sid,
                                         n_now, dLp)
        if entry is None:
            return False
        r = self._register_views(L, bh, Tp, Ap, CTp,
                                 ctab_dev=ctx.get('ctab_dev'))
        oe = np.ctypeslib.as_array(L.amtpu_dom_oe(bh, 0), shape=(1, dTp))
        dom_src = np.ctypeslib.as_array(L.amtpu_fdom_domsrc(bh),
                                        shape=(1, dTp))
        ov = np.ctypeslib.as_array(L.amtpu_dom_ov(bh, 0), shape=(1, dTp))
        n_iters = list_rank.ceil_log2(max(max_obj, 1)) + 1
        # entry.dirty until the post-emit visibility sync lands: a batch
        # that errors in between leaves the device ev unsynced
        entry.dirty = True
        from .resident import (FROM_ENV, _jit_kernel_sharded,
                               _sp_device_cap, _sp_sharding)
        cap = self._resident.sp_cap
        if cap is FROM_ENV:
            cap = _sp_device_cap()
        if _sp_sharding(dLp, count_fenced=True, cap=cap) is not None:
            # multi-device with a capacity the mesh divides AND past the
            # sp fence's long-list crossover: element axis sharded over
            # sp -- the quadratic dominance stage splits across devices
            # (the promoted AMTPU_BENCH_C1_MESH path)
            fn = _jit_kernel_sharded(n_iters, ctx['weff'], 64, cap)
            trace.count('resident.sharded_dispatch')
            trace.metric('mesh.sp_engaged')
        else:
            fn = _jit_kernel(n_iters, ctx['weff'], 64)
        reg_out, rank, combo = telemetry.h2d_call(
            fn, r['g'], r['t'], r['a'], r['s'], r['ctab'], r['cidx'],
            r['d'].astype(bool), np.ones((Tp,), bool), r['si'],
            entry.par, entry.ctr, entry.act, entry.ev,
            np.int32(n_now), oe, dom_src, ov.astype(bool))
        combo.copy_to_host_async()
        touched = np.unique(oe[0][(ov[0] != 0) & (oe[0] >= 0)])
        ctx.update(mode='fused', combo=combo, reg_out=reg_out, rank=rank,
                   resident=(entry, doc_id, obj_sid, n_now,
                             touched.astype(np.int32)))
        trace.count('resident.dispatch')
        # always-on (not AMTPU_TRACE-gated): a bench line labeled
        # `mode: resident` must be able to show residency actually
        # engaged, not silently fell back to the standard fused path
        trace.metric('resident.dispatches')
        return True

    def _mark_resident_stale(self, L, ctx):
        """Invalidates resident entries for every list object this
        (non-resident) batch touched -- its emit updated C++ visibility
        without a device sync."""
        bh = ctx['bh']
        n_blocks = ctx['dims'][6]
        for blk in range(n_blocks):
            bdims = (ctypes.c_int64 * 3)()
            L.amtpu_dom_dims(bh, blk, bdims)
            W = int(bdims[0])
            meta = (ctypes.c_int64 * (4 * W))()
            n_objs = int(L.amtpu_dom_obj_meta(bh, blk, meta))
            for o in range(n_objs):
                doc_idx, obj_sid = int(meta[o * 4]), int(meta[o * 4 + 1])
                doc_id = L.amtpu_batch_doc_id(bh, doc_idx)
                entry = self._resident.entries.get((doc_id, obj_sid))
                if entry is not None:
                    entry.dirty = True
                    trace.count('resident.cross_path_invalidation')

    def _phase_b(self, ctx):
        """Collect device results, run host mid+emit, return patch bytes."""
        L = lib()
        bh = ctx['bh']
        if faults.ARMED:
            # both sites fire BEFORE their phase mutates anything, so a
            # rollback + re-apply reproduces the fault-free byte stream
            if ctx['mode'] != 'hostreg':
                faults.fire('device.collect', ctx.get('fault_docs'))
            faults.fire('native.mid', ctx.get('fault_docs'))
        T, Tp, A, Ap, Larena, Lp, n_blocks, max_obj, CTp = ctx['dims']

        def ip(a):
            return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

        def up(a):
            return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))

        if ctx['mode'] == 'hostreg':
            with trace.span('host.mid'):
                if L.amtpu_mid_hostreg(bh) != 0:
                    _raise_last()
        elif ctx['mode'] == 'fused':
            with trace.span('device.collect'):
                if ctx['combo'] is None:
                    packed = dom_idx = np.zeros(0, np.int32)
                    fallback = False
                    conf_rows = np.zeros(0, np.int32)
                    conf_vals = np.zeros(0, np.int32)
                else:
                    from ..ops import registers as register_ops
                    combo = telemetry.d2h_read(ctx['combo'])
                    packed = np.ascontiguousarray(combo[:Tp])
                    dom_idx = np.ascontiguousarray(combo[Tp:], np.int32)
                    fallback = bool(
                        (packed >> register_ops.PACKED_OVF_SHIFT
                         & 1).any())
                    if not fallback:
                        # conflicts stay SPARSE: only rows whose register
                        # kept >1 member carry a conflict list (the
                        # dense-workload switch lives in
                        # _fetch_conflict_rows)
                        conf_rows = np.nonzero(
                            (packed >> register_ops.PACKED_ALIVE_SHIFT
                             & register_ops.PACKED_ALIVE_MASK)
                            > 1)[0].astype(np.int32)
                        conf_vals = self._fetch_conflict_rows(
                            ctx['reg_out'], conf_rows, Tp)
            if fallback:
                # >window concurrent writers on some register: re-fetch
                # the full outputs + rank, escalate the flagged groups
                # through wider kernel tiers, and hand only what the
                # ladder could not hold (fallback.oracle) to the C++
                # oracle replay
                trace.count('fused.fallback_overflow')
                trace.metric('fallback.overflow_batches')
                trace.metric('fallback.overflow_rows',
                             int((packed >> register_ops.PACKED_OVF_SHIFT
                                  & 1).sum()))
                trace.metric('collect.full_matrix_readback')
                with trace.span('device.collect'):
                    winner, conflicts, alive, overflow = \
                        self._read_register_out(ctx['reg_out'])
                    winner, conflicts, alive, overflow = self._escalate(
                        L, ctx, winner, conflicts, alive, overflow)
                    rank_arr = self._host_rank(ctx['rank'])
                hostdom = ctx.get('hostdom')
                with trace.span('host.mid'):
                    if L.amtpu_mid(bh, ip(winner), ip(conflicts),
                                   self._mid_window(ctx, conflicts),
                                   ip(alive), up(overflow),
                                   None if hostdom else ip(rank_arr),
                                   1 if hostdom else 0) != 0:
                        _raise_last()
                if hostdom:
                    with trace.span('host.dominance'):
                        if L.amtpu_host_dominance(bh) != 0:
                            _raise_last()
                else:
                    with trace.span('device.dominance'):
                        self._run_dominance(L, bh)
            else:
                hostdom = ctx.get('hostdom')
                conf_offs = np.arange(conf_rows.size + 1,
                                      dtype=np.int32) * ctx['weff']
                with trace.span('host.mid'):
                    if L.amtpu_mid_packed(
                            bh, ip(packed), ctx['weff'], ip(conf_rows),
                            ip(conf_offs), ip(conf_vals), len(conf_rows),
                            None, None, None if hostdom else ip(dom_idx),
                            1 if hostdom else 0) != 0:
                        _raise_last()
                if hostdom:
                    with trace.span('host.dominance'):
                        if L.amtpu_host_dominance(bh) != 0:
                            _raise_last()
        else:
            reg_out, rank = ctx['reg_out'], ctx['rank']
            if rank is not None:
                # the rank's read moved here from phase a: count it, and
                # whether the resolver had finished before phase b came
                trace.metric('collect.deferred_rank')
                if rank.is_ready():
                    trace.metric('collect.deferred_rank_ready')
            # Packed member epilogue (ISSUE 3 tentpole a): member-mode
            # batches transfer ONE i32 per register row + a sparse CSR
            # conflict gather instead of the full O(Tp x W) matrices;
            # escalation-tier results merge into the packed word, and
            # only the ladder's residue rides the C++ oracle replay.
            if (Tp > 0 and ctx.get('hovf') is not None
                    and Tp < (1 << 24) and _packed_epilogue_on()):
                with trace.span('device.collect'):
                    (packed, conf_rows, conf_offs, conf_vals,
                     residual) = self._collect_member_packed(
                        ctx, reg_out, Tp)
                    rank_arr = self._host_rank(rank)
                trace.metric('collect.packed_member_batches')
                with trace.span('host.mid'):
                    if L.amtpu_mid_packed(
                            bh, ip(packed), ctx['weff'], ip(conf_rows),
                            ip(conf_offs), ip(conf_vals), len(conf_rows),
                            None if residual is None else up(residual),
                            ip(rank_arr), None, 0) != 0:
                        _raise_last()
            else:
                with trace.span('device.collect'):
                    if Tp > 0:
                        trace.metric('collect.full_matrix_readback')
                        winner, conflicts, alive, overflow = \
                            self._unpack_register_out(reg_out, Tp)
                        if ctx.get('hovf') is not None:
                            # member mode: overflow is host-decided
                            # (>WINDOW concurrent streams / same-change
                            # dup assigns)
                            overflow = np.array(ctx['hovf'], np.uint8)
                            n_ovf = int(overflow.sum())
                            if n_ovf:
                                trace.metric(
                                    'fallback.member_overflow_rows',
                                    n_ovf)
                                trace.metric('fallback.overflow_batches')
                        if overflow.any():
                            winner, conflicts, alive, overflow = \
                                self._escalate(L, ctx, winner, conflicts,
                                               alive, overflow)
                    else:
                        winner = conflicts = alive = np.zeros(0, np.int32)
                        overflow = np.zeros(0, np.uint8)
                    rank_arr = self._host_rank(rank)
                with trace.span('host.mid'):
                    if L.amtpu_mid(bh, ip(winner), ip(conflicts),
                                   self._mid_window(ctx, conflicts),
                                   ip(alive), up(overflow),
                                   ip(rank_arr), 0) != 0:
                        _raise_last()
            with trace.span('device.dominance'):
                self._run_dominance(L, bh)

        with trace.span('host.finish'):
            if L.amtpu_finish(bh) != 0:
                _raise_last()
        if ctx.get('resident') is not None:
            # post-emit visibility sync from the C++ arena ground truth
            entry, doc_id, obj_sid, n_now, touched = ctx['resident']
            self._resident.sync_after_emit(L, self._pool, entry, doc_id,
                                           obj_sid, n_now, touched)
        elif self._resident.entries:
            # a NON-resident batch may have flipped visibility on arenas
            # the cache holds (multi-object batches, member-window mode,
            # overflow); mark every overlapping entry stale
            self._mark_resident_stale(L, ctx)
        if trace.ENABLED:
            tr = (ctypes.c_double * 6)()
            L.amtpu_batch_trace(bh, tr)
            for name, val in zip(('decode', 'schedule', 'encode',
                                  'mid', 'emit', 'domlay'), tr):
                trace.add('cxx.' + name, float(val))
            sc = (ctypes.c_int64 * 4)()
            L.amtpu_sched_counts(bh, sc)
            trace.count('sched.fast_path', int(sc[0]))
            trace.count('sched.queued', int(sc[1]))
            if sc[2]:
                trace.count('sched.trivial_rows', int(sc[2]))
                trace.count('sched.trivial_groups', int(sc[3]))
        out_len = ctypes.c_int64()
        ptr = L.amtpu_result(bh, ctypes.byref(out_len))
        return ctypes.string_at(ptr, out_len.value) \
            if out_len.value else b'\x80'

    @staticmethod
    def _host_rank(rank):
        """The list rank amtpu_mid takes, np.int32 [Lp], from the device
        rank a dispatch left in flight (None: the batch has no list
        work).  Callers sit inside a `device.collect` span."""
        if rank is None:
            return np.zeros(0, np.int32)
        return np.ascontiguousarray(telemetry.d2h_read(rank), np.int32)

    @staticmethod
    def _mid_window(ctx, conflicts):
        """Conflicts-matrix width handed to amtpu_mid: the escalation
        merge may have widened it beyond the dispatch window."""
        return int(conflicts.shape[1]) if conflicts.ndim == 2 \
            else ctx['weff']

    def _esc_layout_groups(self, L, bh):
        """CSR group records from the C++ escalation layout
        (amtpu_esc_*), built at begin for member-mode overflow --
        replaces the host-side window re-derivation.  None when the
        batch carries no layout (sliding-mode overflow, AMTPU_WEFF)."""
        dims = (ctypes.c_int64 * 3)()
        L.amtpu_esc_dims(bh, dims)
        n_groups, R, M = [int(x) for x in dims]
        if n_groups == 0:
            return None
        meta = np.ctypeslib.as_array(L.amtpu_esc_group_meta(bh),
                                     shape=(n_groups, 3))
        rows_all = np.ctypeslib.as_array(L.amtpu_esc_rows(bh), shape=(R,))
        off = np.ctypeslib.as_array(L.amtpu_esc_mem_off(bh),
                                    shape=(R + 1,))
        vals_all = np.ctypeslib.as_array(L.amtpu_esc_mem(bh),
                                         shape=(M,)) if M else \
            np.zeros(0, np.int32)
        groups = []
        for gi in range(n_groups):
            rs, k, width = (int(meta[gi, 0]), int(meta[gi, 1]),
                            int(meta[gi, 2]))
            groups.append((rows_all[rs:rs + k],
                           np.diff(off[rs:rs + k + 1]),
                           vals_all[off[rs]:off[rs + k]], width))
        return groups

    def _escalation_dispatch(self, L, ctx, flagged):
        """Tier-ladder dispatch for this batch's flagged rows: prefers
        the C++-prebuilt member layout; falls back to the generic host
        window build (sliding-mode overflow has no layout)."""
        from ..ops import registers as register_ops
        Tp, Ap = ctx['dims'][1], ctx['dims'][3]
        CTp = ctx['dims'][8]
        r = self._register_views(L, ctx['bh'], Tp, Ap, CTp,
                                 ctab_dev=ctx.get('ctab_dev'))
        groups = self._esc_layout_groups(L, ctx['bh'])
        if groups is not None:
            return register_ops.escalate_dispatch_groups(
                groups, r['t'], r['a'], r['s'], r['d'].astype(bool),
                r['ctab'], r['cidx'], want_visible_before=False)
        return register_ops.escalate_overflow_dispatch(
            r['g'], r['t'], r['a'], r['s'], r['d'].astype(bool),
            r['ctab'], r['cidx'], flagged, want_visible_before=False)

    def _escalate(self, L, ctx, winner, conflicts, alive, overflow):
        """Tiered escalation ladder over the batch's register columns:
        collects the tier dispatches (pre-dispatched async in phase a
        when the flags were host-computed, dispatched here otherwise)
        and merges the results, clearing the flags of resolved rows.
        Rows still flagged afterwards -- groups wider than every tier /
        over the scratch budget, or all of them under AMTPU_ESCALATE=0
        -- take the C++ oracle replay in amtpu_mid and are counted as
        fallback.oracle."""
        from ..ops import registers as register_ops
        esc = ctx.pop('esc', None)
        if esc is None and register_ops.escalation_enabled():
            esc = self._escalation_dispatch(L, ctx,
                                            overflow.astype(bool))
        if esc is not None:
            chunks = register_ops.escalate_overflow_collect_arrays(esc[0])
            if chunks:
                winner = np.array(winner, np.int32)
                conflicts = np.array(conflicts, np.int32)
                alive = np.array(alive, np.int32)
                overflow = np.array(overflow, np.uint8)
                winner, conflicts, alive, overflow = \
                    register_ops.merge_escalated_arrays(
                        winner, conflicts, alive, overflow, chunks)
        n_oracle = int(np.asarray(overflow, bool).sum())
        if n_oracle:
            trace.metric('fallback.oracle', n_oracle)
        return winner, conflicts, alive, overflow

    def _collect_member_packed(self, ctx, reg_out, Tp):
        """Packed member epilogue (ISSUE 3 tentpole a): ONE [Tp] i32
        word + a sparse CSR conflict gather cross the device boundary
        instead of the full winner/conflicts/alive/overflow matrices
        (_unpack_register_out).  Escalation-tier results merge INTO the
        packed word host-side -- their conflicts ride the same CSR at
        tier width -- and rows the ladder could not resolve stay flagged
        in the returned residual vector for the C++ oracle replay
        (fallback.oracle).

        Returns (packed [Tp] i32, conf_rows, conf_offs, conf_vals,
        residual u8 [Tp] | None)."""
        from ..ops import registers as register_ops
        flagged = np.asarray(ctx['hovf']).astype(bool)
        residual = None
        esc_parts = []            # (global rows, global conflicts) pairs
        esc = None
        if flagged.any():
            trace.metric('fallback.member_overflow_rows',
                         int(flagged.sum()))
            trace.metric('fallback.overflow_batches')
            esc = ctx.pop('esc', None)
            if esc is None and register_ops.escalation_enabled():
                # flags are host-computed, so phase a normally
                # pre-dispatched the tiers; dispatch late if it could not
                esc = self._escalation_dispatch(lib(), ctx, flagged)
        # Device-side tier merge (ISSUE 6 tentpole b): scatter each tier
        # chunk's packed words into the base word ON DEVICE -- tier-local
        # winners translate to global rows through the chunk's row map --
        # so the ONE packed transfer below returns the word already
        # resolved for every tier-escalated row; the host's remaining
        # merge work is the residual vector + sparse conflicts.
        dev_merge = (esc is not None and len(esc[0]) > 0
                     and register_ops.device_merge_on())
        if dev_merge:
            base = reg_out['packed']
            for _W, sub_rows, out in esc[0]:
                Tn = int(out['packed'].shape[0])
                rows_p = np.full(Tn, Tp, np.int32)       # Tp = dropped
                rows_p[:len(sub_rows)] = sub_rows
                sub_p = np.zeros(Tn, np.int32)
                sub_p[:len(sub_rows)] = sub_rows
                base = telemetry.h2d_call(
                    register_ops.merge_packed_rows_jit(),
                    base, rows_p, out['packed'], sub_p)
            trace.metric('collect.device_merge_chunks', len(esc[0]))
            packed = telemetry.d2h_read(base)
        else:
            packed = telemetry.d2h_read(reg_out['packed'])
        if flagged.any():
            if not dev_merge:
                packed = np.array(packed)        # writable copy
            residual = np.array(np.asarray(ctx['hovf']), np.uint8)
            if esc is not None:
                for ch in register_ops.escalate_overflow_collect_arrays(
                        esc[0], need_winner=not dev_merge):
                    if not dev_merge:
                        packed[ch.rows] = register_ops.pack_register_word(
                            ch.winner, ch.alive)
                    residual[ch.rows] = 0
                    if ch.conf_rows.size:
                        esc_parts.append((ch.rows[ch.conf_rows],
                                          ch.conflicts))
            n_oracle = int(residual.sum())
            if n_oracle:
                trace.metric('fallback.oracle', n_oracle)
            else:
                residual = None
        # base sparse conflicts: rows OUTSIDE flagged groups that kept
        # more than one member (flagged groups' base-kernel output is
        # invalid -- they re-resolved in the tiers or the oracle replay)
        base_mask = ((packed >> register_ops.PACKED_ALIVE_SHIFT)
                     & register_ops.PACKED_ALIVE_MASK) > 1
        if flagged.any():
            base_mask &= ~flagged
        conf_rows_b = np.nonzero(base_mask)[0].astype(np.int32)
        conf_vals_b = self._fetch_conflict_rows(reg_out, conf_rows_b, Tp)
        weff = ctx['weff']
        if not esc_parts:
            conf_offs = np.arange(conf_rows_b.size + 1,
                                  dtype=np.int32) * weff
            conf_vals = np.ascontiguousarray(conf_vals_b, np.int32) \
                .reshape(-1)
            return packed, conf_rows_b, conf_offs, conf_vals, residual
        rows_parts = [conf_rows_b]
        vals_parts = [np.ascontiguousarray(conf_vals_b,
                                           np.int32).reshape(-1)]
        lens = [np.full(conf_rows_b.size, weff, np.int32)]
        for rows_g, conf_g in esc_parts:
            rows_parts.append(np.ascontiguousarray(rows_g, np.int32))
            vals_parts.append(np.ascontiguousarray(conf_g,
                                                   np.int32).reshape(-1))
            lens.append(np.full(rows_g.size, conf_g.shape[1], np.int32))
        conf_rows = np.ascontiguousarray(np.concatenate(rows_parts),
                                         np.int32)
        conf_offs = np.zeros(conf_rows.size + 1, np.int32)
        np.cumsum(np.concatenate(lens), out=conf_offs[1:])
        conf_vals = np.ascontiguousarray(np.concatenate(vals_parts),
                                         np.int32)
        return packed, conf_rows, conf_offs, conf_vals, residual

    def _fetch_conflict_rows(self, reg_out, conf_rows, Tp):
        """Sparse-vs-dense conflicts fetch: the device row gather wins
        while >1-member rows are rare; once `conf_rows * thresh > Tp`
        (AMTPU_CONF_DENSE_THRESH, default 4; 0 disables the dense path)
        the whole [Tp, W] matrix transfers once and slices host-side
        instead.  Each choice is counted: collect.conflict_sparse /
        collect.conflict_dense."""
        thresh = _conf_dense_thresh()
        if thresh and conf_rows.size * thresh > Tp:
            trace.metric('collect.conflict_dense')
            allconf = telemetry.d2h_read(reg_out['conflicts'])
            return np.ascontiguousarray(allconf[conf_rows], np.int32)
        if conf_rows.size:
            trace.metric('collect.conflict_sparse')
        return self._gather_conflict_rows(reg_out, conf_rows)

    def _gather_conflict_rows(self, reg_out, rows):
        """Lazy conflicts fetch: only registers that kept >1 member have
        conflict rows worth transferring.  Returns [n, WINDOW] i32."""
        from ..ops import registers as register_ops
        if not rows.size:
            return np.zeros(0, np.int32)
        pad = 1
        while pad < rows.size:
            pad *= 2
        rows_p = np.zeros((pad,), np.int32)
        rows_p[:rows.size] = rows
        got = telemetry.d2h_read(telemetry.h2d_call(
            register_ops.gather_rows, reg_out['conflicts'],
            rows_p))[:rows.size]
        return np.ascontiguousarray(got, np.int32)

    def _gather_conflicts(self, reg_out, alive, Tp):
        """Dense [Tp, W] conflicts (fallback paths); width follows the
        kernel's conflicts output (the dynamic window)."""
        width = int(reg_out['conflicts'].shape[1])
        conflicts = np.full((Tp, width), -1, np.int32)
        rows = np.nonzero(alive > 1)[0].astype(np.int32)
        got = self._gather_conflict_rows(reg_out, rows)
        if rows.size:
            conflicts[rows] = got
        return conflicts

    # -- kernel dispatch ------------------------------------------------

    def _run_resolver(self, L, bh, Tp, Ap, CTp, Lp, max_obj_len,
                      mem=None, weff=None, ctab_dev=None):
        """Register resolution + linearization, fused into one dispatch
        when both are needed (halves blocking round trips on the
        high-latency device link).  Returns (reg_out device dict | None,
        rank device array [Lp] | None).  Nothing here blocks: the rank's
        transfer is started and phase b reads it where amtpu_mid needs
        it, so a wave's resolver runs on the device while the next
        wave's C++ begin runs on the host."""
        from ..ops import list_rank, registers as register_ops
        if Tp > 0:
            r = self._register_views(L, bh, Tp, Ap, CTp,
                                     ctab_dev=ctab_dev)
        if Lp > 0:
            e = self._arena_views(L, bh, Lp)
            # doubling depth: DFS chains never cross objects
            n_iters = list_rank.ceil_log2(max(max_obj_len, 1)) + 1
        if Tp > 0:
            trace.count('ops.registers.members' if mem is not None
                        else 'ops.registers.xla')
        if Tp > 0 and Lp > 0:
            reg_out, rank = telemetry.h2d_call(
                register_ops.resolve_and_rank,
                r['g'], r['t'], r['a'], r['s'], r['ctab'], r['cidx'],
                r['d'].astype(bool), np.ones((Tp,), bool), r['si'],
                e['obj'], e['par'], e['ctr'], e['act'],
                e['val'].astype(bool), e['lsi'], n_iters,
                window=weff, mem_idx=mem)
            rank.copy_to_host_async()
            return reg_out, rank
        if Tp > 0:
            if mem is not None:
                reg_out = telemetry.h2d_call(
                    register_ops.resolve_registers_members,
                    r['t'], r['a'], r['s'], mem, r['d'].astype(bool),
                    r['ctab'], r['cidx'], window=weff,
                    want_visible_before=False)
            else:
                reg_out = telemetry.h2d_call(
                    register_ops.resolve_registers,
                    r['g'], r['t'], r['a'], r['s'],
                    is_del=r['d'].astype(bool),
                    alive_in=np.ones((Tp,), bool), window=weff,
                    sort_idx=r['si'], clock_table=r['ctab'],
                    clock_idx=r['cidx'])
            return reg_out, None
        if Lp > 0:
            rank = telemetry.h2d_call(list_rank.linearize,
                                      e['obj'], e['par'], e['ctr'], e['act'],
                                      e['val'].astype(bool), n_iters,
                                      sort_idx=e['lsi'])
            rank.copy_to_host_async()
            return None, rank
        return None, None

    def _unpack_register_out(self, reg_out, Tp):
        """One packed [Tp] i32 transfer for winner/alive/overflow plus a
        lazy row-gather of conflicts only where a register kept >1 member
        (D2H over the device link is the scarce resource, not compute)."""
        if Tp >= 1 << 24:    # packed winner field width exceeded
            return self._read_register_out(reg_out)
        packed = telemetry.d2h_read(reg_out['packed'])
        winner, alive, overflow = self._unpack_packed(packed)
        conflicts = self._gather_conflicts(reg_out, alive, Tp)
        return winner, conflicts, alive, overflow

    @staticmethod
    def _read_register_out(reg_out):
        """The full winner/conflicts/alive/overflow matrices, read back
        whole (the fallback paths)."""
        read = telemetry.d2h_read
        return (np.ascontiguousarray(read(reg_out['winner']), np.int32),
                np.ascontiguousarray(read(reg_out['conflicts']), np.int32),
                np.ascontiguousarray(read(reg_out['alive_after']), np.int32),
                np.ascontiguousarray(read(reg_out['overflow']), np.uint8))

    @staticmethod
    def _unpack_packed(packed):
        """Splits the packed [T] i32 register summary (24-bit winner,
        PACKED_WINNER_NONE = none | 6-bit alive, saturated at
        PACKED_ALIVE_MAX | overflow in bit PACKED_OVF_SHIFT) -- the
        decode twin of ops/registers.pack_register_word; both sides read
        the layout from the shared PACKED_* constants."""
        from ..ops import registers as register_ops
        winner = np.ascontiguousarray(
            packed & register_ops.PACKED_WINNER_MASK, np.int32)
        winner[winner == register_ops.PACKED_WINNER_NONE] = -1
        alive = np.ascontiguousarray(
            (packed >> register_ops.PACKED_ALIVE_SHIFT)
            & register_ops.PACKED_ALIVE_MASK, np.int32)
        overflow = np.ascontiguousarray(
            (packed >> register_ops.PACKED_OVF_SHIFT) & 1, np.uint8)
        return winner, alive, overflow

    def _run_dominance(self, L, bh):
        """Fallback-path dominance: per size-class device dispatches using
        the host-filled er/orank/od mirrors (after amtpu_mid).  Blocks are
        one-per-class since begin; classes too wide for one dispatch are
        sliced along the object axis here (numpy views are cheap)."""
        from ..ops.pallas_dominance import dominance_grouped_auto
        dims = (ctypes.c_int64 * self.N_DIMS)()
        L.amtpu_batch_dims(bh, dims)
        n_blocks = int(dims[6])
        bdims = (ctypes.c_int64 * 3)()
        CAP = 256 << 20
        for blk in range(n_blocks):
            L.amtpu_dom_dims(bh, blk, bdims)
            W, Lp, Tp = [int(x) for x in bdims]
            v0 = np.ctypeslib.as_array(L.amtpu_dom_v0(bh, blk),
                                       shape=(W, Lp))
            er = np.ctypeslib.as_array(L.amtpu_dom_er(bh, blk),
                                       shape=(W, Lp))
            oe = np.ctypeslib.as_array(L.amtpu_dom_oe(bh, blk),
                                       shape=(W, Tp))
            orank = np.ctypeslib.as_array(L.amtpu_dom_orank(bh, blk),
                                          shape=(W, Tp))
            od = np.ctypeslib.as_array(L.amtpu_dom_od(bh, blk),
                                       shape=(W, Tp))
            ov = np.ctypeslib.as_array(L.amtpu_dom_ov(bh, blk),
                                       shape=(W, Tp))
            w_cap = max(1, min(CAP // (Lp * 64 * 4), CAP // (Tp * 4)))
            if W <= w_cap:
                idx = telemetry.h2d_call(dominance_grouped_auto, v0, er, oe,
                                         orank, od, ov.astype(bool), chunk=64)
                with trace.span('device.collect'):
                    idx = telemetry.d2h_read(idx)
            else:
                idx = np.empty((W, Tp), np.int32)
                for s in range(0, W, w_cap):
                    hi = min(W, s + w_cap)
                    n = hi - s

                    def pad(x, fill):
                        if n == w_cap:
                            return x[s:hi]
                        out = np.full((w_cap,) + x.shape[1:], fill,
                                      x.dtype)
                        out[:n] = x[s:hi]
                        return out

                    got = telemetry.h2d_call(
                        dominance_grouped_auto,
                        pad(v0, 0.0), pad(er, -1), pad(oe, -1),
                        pad(orank, -1), pad(od, 0),
                        pad(ov, 0).astype(bool), chunk=64)
                    with trace.span('device.collect'):
                        idx[s:hi] = telemetry.d2h_read(got)[:n]
            idx = np.ascontiguousarray(idx, np.int32)
            L.amtpu_dom_set_indexes(
                bh, blk, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))

    # -- dict-level API (the scalar oracle's change and patch dicts) ----

    _doc_key = staticmethod(doc_key)

    def apply_batch_bytes_resilient(self, payload):
        """`apply_batch_bytes` behind the resilience layer: transient
        failures retry with backoff, persistent ones bisect down to the
        poison doc(s), which quarantine as per-doc error envelopes while
        every healthy doc commits (docs/RESILIENCE.md)."""
        from .. import resilience
        return resilience.apply_payload(self, payload)

    def apply_batch(self, changes_by_doc):
        return _apply_batch_dicts(self, changes_by_doc)

    def apply_changes(self, doc_id, changes):
        out = self.apply_batch({doc_id: changes})[doc_id]
        _raise_if_quarantined(doc_id, out)
        return out

    def apply_local_change(self, doc_id, request):
        """Applies one local change request with the reference's undo
        semantics (backend/index.js:175-197): requestType 'change' records
        inverse ops on the per-doc undo stack; 'undo'/'redo' execute the
        stacks.  Returns the patch (incl. actor/seq and real
        canUndo/canRedo)."""
        key = self._doc_key(doc_id)
        payload = msgpack.packb(request, use_bin_type=True)
        # local changes latch the C++ statics / jit caches exactly like
        # batches do, so they must take (or check) the same snapshot --
        # a gateway that serves local changes first would otherwise
        # baseline the latch on post-flip values
        _check_resident_latch()
        self._ensure_mode_flags()
        with trace.span('host.begin'):
            bh = lib().amtpu_begin_local(self._pool, key.encode(), payload,
                                         len(payload))
        if not bh:
            _raise_last()
        _track_begin()
        if faults.ARMED:
            try:
                faults.fire('native.begin', [key])
            except Exception as e:
                _rollback_batch(bh, e)
                _free_batch(bh)
                raise
        ctx = self._phase_a_rest(bh, [key] if faults.ARMED else None)
        try:
            out = self._phase_b(ctx)
        except Exception as e:
            _unwind_batch(ctx, e)
            raise
        finally:
            _free_batch(bh)
        return msgpack.unpackb(out, raw=False, strict_map_key=False)[key]

    def get_patch(self, doc_id):
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_get_patch(
            self._pool, self._doc_key(doc_id).encode(),
            ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        return msgpack.unpackb(_take_buf(ptr, out_len.value), raw=False)

    def get_clock(self, doc_id):
        """{'clock': ..., 'deps': ...} without materializing the doc --
        the cheap per-round query replica catch-up gossips."""
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_get_clock(
            self._pool, self._doc_key(doc_id).encode(),
            ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        return msgpack.unpackb(_take_buf(ptr, out_len.value), raw=False)

    def _tail_raws(self, key):
        """Raw msgpack bytes of the changes the C++ arena still holds
        for `key` (the post-truncation tail), application order."""
        from .. import storage
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_save(self._pool, key.encode(),
                               ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        raw_v1 = _take_buf(ptr, out_len.value)
        return storage.split_changes_array(
            memoryview(raw_v1)[len(_CKPT_PREFIX):])

    def _snapshot_raws(self, st):
        from .. import storage
        out = []
        for chunk in st['chunks']:
            out.extend(storage.decode_columnar(chunk))
        return out

    def save(self, doc_id):
        """Checkpoint one doc as msgpack bytes: by default the v2
        COLUMNAR container (settled snapshot chunks + delta/RLE-encoded
        tail, docs/STORAGE.md) -- compacted docs reuse their cached
        snapshot bytes, so save cost is O(tail), not O(history).
        ``AMTPU_STORAGE_FORMAT=json`` emits the PR-4 v1 container (raw
        change history, the parity oracle).  Load with `load()` on any
        pool; both formats restore byte-identically (the reference's
        save serializes opSet.history, src/automerge.js:45-52)."""
        from .. import storage
        key = self._doc_key(doc_id)
        st = self._storage.get(key)
        tail = self._tail_raws(key)
        if storage.storage_format() == 'json':
            if not st or not st['chunks']:
                return storage.pack_checkpoint_v1(tail)
            # parity-oracle arm of a doc compacted earlier (format
            # flipped mid-process / v2 blob loaded): reconstruct the
            # full v1 history
            return storage.pack_checkpoint_v1(
                self._snapshot_raws(st) + tail)
        frontier = dict(st['frontier']) if st else {}
        chunks = list(st['chunks']) if st else []
        return storage.pack_checkpoint(frontier, chunks, tail)

    def load(self, doc_id, data):
        """Restores a `save()` checkpoint (either container format) as
        ONE batched replay (the reference replays scalar, O(history)
        through a fresh backend -- here the whole history resolves in a
        single kernel pass).  A v2 container's settled snapshot is re-
        adopted, so a reloaded doc stays compacted.  Returns the doc's
        whole-state patch."""
        from .. import storage
        if not storage.is_checkpoint(data):
            from ..errors import RangeError
            raise RangeError('not an amtpu-doc checkpoint')
        _load_batch(self, {doc_id: data})
        return self.get_patch(doc_id)

    def load_batch(self, blobs):
        """Restores MANY save() checkpoints in one batched replay
        ({doc_id: bytes}); the whole DocSet resolves in a single kernel
        pass instead of one device round trip per doc."""
        _load_batch(self, blobs)

    def restore_from_store(self, store, doc_ids=None, batch=None,
                           threads=None):
        """Restores the store's whole manifest inventory into this pool
        (module-level `restore_from_store`; a single pool applies
        serially with the next batch's blob reads prefetching)."""
        return restore_from_store(self, store, doc_ids=doc_ids,
                                  batch=batch, threads=threads)

    def get_missing_deps(self, doc_id):
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_get_missing_deps(
            self._pool, self._doc_key(doc_id).encode(),
            ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        return msgpack.unpackb(_take_buf(ptr, out_len.value), raw=False)

    def _missing_clock(self, key, have_deps):
        """The transitively-closed {actor: from_seq} clock the C++
        missing-changes walk serves from (the same closure, exposed)."""
        have = msgpack.packb(dict(have_deps), use_bin_type=True)
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_get_missing_clock(
            self._pool, key.encode(), have, len(have),
            ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        return msgpack.unpackb(_take_buf(ptr, out_len.value), raw=False)

    def _missing_changes_raw(self, key, have_deps):
        have = msgpack.packb(dict(have_deps), use_bin_type=True)
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_get_missing_changes(
            self._pool, key.encode(), have, len(have),
            ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        return _take_buf(ptr, out_len.value)

    def get_missing_changes(self, doc_id, have_deps):
        """Changes the requester is missing given its `have_deps`
        clock.  A doc compacted behind the settled frontier serves a
        straggler (whose closure reaches into the snapshot) by merging
        snapshot-decoded changes with the C++ tail, in exactly the
        order the untruncated walk would have produced -- byte parity
        is the GC-frontier test lane's contract (docs/STORAGE.md)."""
        from .. import storage
        key = self._doc_key(doc_id)
        st = self._storage.get(key)
        if st and st['chunks']:
            from_clock = self._missing_clock(key, have_deps)
            if any(from_clock.get(a, 0) < s
                   for a, s in st['frontier'].items()):
                telemetry.metric('storage.snapshot_backfills')
                raws = self._merged_missing_raws(key, st, from_clock)
                return [msgpack.unpackb(r, raw=False,
                                        strict_map_key=False)
                        for r in raws]
        return msgpack.unpackb(self._missing_changes_raw(key, have_deps),
                               raw=False)

    def _merged_missing_raws(self, key, st, from_clock):
        """Snapshot + tail merge: per actor in first-seen application
        order, changes with seq > from_clock[actor], seq ascending --
        the exact emission order of the C++ walk over full history."""
        from .. import storage
        full = []
        for chunk in st['chunks']:
            full.extend(storage.decode_columnar_meta(chunk))
        for raw in self._tail_raws(key):
            c = msgpack.unpackb(raw, raw=False, strict_map_key=False)
            full.append((raw, c.get('actor'), c.get('seq')))
        actor_order, per_actor = [], {}
        for raw, actor, seq in full:
            if actor not in per_actor:
                actor_order.append(actor)
                per_actor[actor] = []
            per_actor[actor].append((seq, raw))
        out = []
        for actor in actor_order:
            frm = from_clock.get(actor, 0)
            out.extend(raw for seq, raw in per_actor[actor]
                       if seq is not None and seq > frm)
        return out

    def get_register(self, doc_id, obj, key):
        """Current field ops of one (obj, key), winner first -- the
        Backend.getFieldOps query undo/redo capture reads."""
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_get_register(
            self._pool, self._doc_key(doc_id).encode(), obj.encode(),
            key.encode(), ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        return msgpack.unpackb(_take_buf(ptr, out_len.value), raw=False)

    def get_changes_for_actor(self, doc_id, actor, after_seq=0):
        """(parity: op_set.js:347-357)"""
        return msgpack.unpackb(
            self.get_changes_for_actor_bytes(doc_id, actor, after_seq),
            raw=False)

    def get_changes_for_actor_bytes(self, doc_id, actor, after_seq=0):
        """Raw msgpack array of changes -- the zero-decode shipping path
        replica catch-up uses (change bytes pass sender -> receiver
        without ever becoming Python objects).  Compacted docs splice
        snapshot-decoded raws ahead of the C++ tail (decode_columnar is
        byte-lossless, so the shipped bytes are identical either way)."""
        from .. import storage
        key = self._doc_key(doc_id)
        out_len = ctypes.c_int64()
        ptr = lib().amtpu_get_changes_for_actor(
            self._pool, key.encode(), actor.encode(),
            after_seq, ctypes.byref(out_len))
        if not ptr:
            _raise_last()
        buf = _take_buf(ptr, out_len.value)
        st = self._storage.get(key)
        if not st or not st['chunks'] \
                or after_seq >= st['frontier'].get(actor, 0):
            return buf
        telemetry.metric('storage.snapshot_backfills')
        head = []
        for chunk in st['chunks']:
            for raw, a, seq in storage.decode_columnar_meta(chunk):
                if a == actor and seq is not None and seq > after_seq:
                    head.append(raw)
        return storage.join_changes_array(
            head + storage.split_changes_array(buf))

    def _apply_columnar(self, payload):
        """One arena-direct columnar batch (`amtpu_begin_columnar`):
        payload is msgpack {doc_key: [part, ...]} where each part is a
        columnar blob or a raw msgpack changes array.  The batch is
        pinned host-full in C++, so phase b is the hostreg driver
        regardless of exec mode (host/kernel byte parity is pinned by
        the differential suites)."""
        L = lib()
        _check_resident_latch()
        self._ensure_mode_flags()
        t0 = time.perf_counter()
        with trace.span('host.begin'):
            bh = L.amtpu_begin_columnar(self._pool, payload,
                                        len(payload))
        if not bh:
            _raise_last()
        _track_begin()
        telemetry.metric('storage.native_loads')
        ctx = self._phase_a_rest(bh)
        t1 = time.perf_counter()
        attribution.note_flush_phase('dispatch', t1 - t0)
        try:
            return self._phase_b(ctx)
        except Exception as e:
            _rollback_batch(ctx['bh'], e)
            raise
        finally:
            attribution.note_flush_phase('collect',
                                         time.perf_counter() - t1)
            _free_batch(ctx['bh'])

    # -- settled-history GC + cold-doc eviction (ISSUE 10) ---------------

    def _adopt_snapshot(self, key, frontier, chunks):
        """Installs a checkpoint's settled snapshot for `key` and
        truncates the C++ arena behind its frontier (reload keeps the
        compacted economics; docs/STORAGE.md).  Op-state folding rides
        along, so a reloaded doc's op arena stays as lean as the one it
        checkpointed from."""
        self._storage[key] = {'frontier': dict(frontier),
                              'chunks': list(chunks)}
        self._truncate(key, frontier)
        self._fold_settled(key, frontier)
        self._fold_clocks(key, frontier)

    def _truncate(self, key, frontier):
        fb = msgpack.packb(dict(frontier), use_bin_type=True)
        freed = lib().amtpu_truncate_history(self._pool, key.encode(),
                                             fb, len(fb))
        if freed < 0:
            _raise_last()
        telemetry.metric('storage.gc.bytes_freed', freed)
        return freed

    def compact(self, doc_id, frontier=None, min_changes=0):
        """Folds the causally-settled PREFIX of the doc's history into
        its columnar snapshot and truncates the arena behind it.

        `frontier` is the settled {actor: seq} clock (every peer's
        acked coverage -- the gateway passes the fan-out engine's
        pointwise-min believed clock); None means no external
        constraint (no live subscribers), i.e. everything applied is
        settled.  Only the longest history PREFIX at or behind the
        frontier folds: application order is part of the materialize
        contract (concurrent changes resolve key order by arrival), so
        the snapshot must stay an exact order-preserving prefix.
        Returns the number of changes folded (0 = nothing to do;
        ``AMTPU_STORAGE_FORMAT=json`` makes this a no-op, the parity
        -oracle arm)."""
        from .. import storage
        key = self._doc_key(doc_id)
        if storage.storage_format() == 'json':
            telemetry.metric('storage.gc.skipped_json')
            return 0
        clock = self.get_clock(doc_id).get('clock') or {}
        if not clock:
            return 0
        if frontier is None:
            limit = dict(clock)
        else:
            limit = {}
            for a, s in frontier.items():
                s = min(int(s), int(clock.get(a, 0)))
                if s > 0:
                    limit[a] = s
            if not limit:
                return 0
        tail = self._tail_raws(key)
        fold, prefix_clock = [], {}
        for raw in tail:
            c = msgpack.unpackb(raw, raw=False, strict_map_key=False)
            actor, seq = c.get('actor'), c.get('seq', 0)
            if seq > limit.get(actor, 0):
                break            # first unsettled change ends the prefix
            fold.append(raw)
            prefix_clock[actor] = max(prefix_clock.get(actor, 0), seq)
        if not fold or len(fold) < min_changes:
            return 0
        st = self._storage.setdefault(key, {'frontier': {},
                                            'chunks': []})
        st['chunks'].append(storage.encode_columnar(fold))
        for a, s in prefix_clock.items():
            st['frontier'][a] = max(st['frontier'].get(a, 0), s)
        self._truncate(key, st['frontier'])
        self._fold_settled(key, st['frontier'])
        self._fold_clocks(key, st['frontier'])
        self._maybe_rechunk(key, st)
        telemetry.metric('storage.gc.compactions')
        telemetry.metric('storage.gc.changes_folded', len(fold))
        return len(fold)

    def _fold_settled(self, key, frontier):
        """Op-state folding (ISSUE 14 tentpole): settled changes at or
        behind `frontier` free their op records / deps / message in the
        C++ arena -- registers and list arenas already hold their final
        values, and the columnar snapshot holds their replay bytes, so
        the arena stops growing with history under settled-overwrite
        churn.  ``AMTPU_STORAGE_FOLD=0`` is the no-fold A/B arm the
        folding lane compares against (byte-identical patches and
        straggler backfills either way)."""
        if not frontier or not env_bool('AMTPU_STORAGE_FOLD', True):
            return 0
        fb = msgpack.packb(dict(frontier), use_bin_type=True)
        n = lib().amtpu_fold_settled(self._pool, key.encode(), fb,
                                     len(fb))
        if n < 0:
            _raise_last()
        if n:
            telemetry.metric('storage.gc.ops_folded', n)
        return int(n)

    def _fold_clocks(self, key, frontier):
        """Clock-vector folding (ISSUE 17 tentpole): settled changes at
        or behind `frontier` move their sparse per-change ``all_deps``
        vector clocks into the doc's densified C++ fold table (or a
        zero-byte sentinel for empty / linear-history shapes) and free
        the vectors -- the last per-history memory term goes O(live
        frontier) instead of O(changes).  Causal queries (straggler
        closure walks, `get_missing_clock`, conflict concurrency) keep
        answering through the folded rows -- the clock-fold parity
        suite pins them against an unfolded twin.
        ``AMTPU_STORAGE_FOLD_CLOCKS=0`` is the unfolded A/B arm;
        ``AMTPU_FOLDCLK_MAX_ACTORS`` (default 256) caps the per-doc
        folded actor population (row width is the doc's actor count --
        past the cap, non-trivial vectors stay sparse)."""
        if not frontier or \
                not env_bool('AMTPU_STORAGE_FOLD_CLOCKS', True):
            return 0
        fb = msgpack.packb(dict(frontier), use_bin_type=True)
        n = lib().amtpu_fold_clocks(
            self._pool, key.encode(), fb, len(fb),
            env_int('AMTPU_FOLDCLK_MAX_ACTORS', 256))
        if n < 0:
            _raise_last()
        if n:
            telemetry.metric('storage.gc.clocks_folded', n)
        return int(n)

    def clock_pairs(self, doc_id=None):
        """Retained sparse all_deps clock pairs (one doc, or the whole
        pool), walked fresh in C++ -- the reconciliation oracle the
        clock-fold lane gates against `doc_stats`'s incrementally-
        maintained ``clk_pairs`` column."""
        key = '' if doc_id is None else self._doc_key(doc_id)
        n = lib().amtpu_clock_pairs(self._pool, key.encode())
        if n < 0:
            _raise_last()
        return int(n)

    def resclk_row_bytes(self):
        """Bytes one pool-resident clock-table row costs (padded actor
        width x int32) -- converts `doc_stats`'s ``resclk_rows`` count
        into the byte tier the capacity cost vector reports."""
        info = (ctypes.c_int64 * 4)()
        lib().amtpu_resclk_info(self._pool, info)
        return int(info[1]) * 4

    def _maybe_rechunk(self, key, st):
        """Chunk re-compaction (ISSUE 14): a long-lived doc accumulates
        one snapshot chunk per GC fold; past ``AMTPU_STORAGE_CHUNK_MAX``
        chunks (default 8; 0 disables) they merge into one columnar
        blob on the same `_storage_upkeep` cadence that triggered the
        fold.  Decode is byte-lossless, so the merged chunk replays and
        backfills byte-identically."""
        from .. import storage
        cap = env_int('AMTPU_STORAGE_CHUNK_MAX', 8)
        if cap <= 0 or len(st['chunks']) < cap:
            return 0
        raws = []
        for chunk in st['chunks']:
            raws.extend(storage.decode_columnar(chunk))
        st['chunks'] = [storage.encode_columnar(raws)]
        telemetry.metric('storage.gc.rechunks')
        return len(raws)

    def op_count(self, doc_id=None):
        """Retained op records in the C++ arena (applied states + the
        causal queue; one doc or the whole pool) -- the growth measure
        the op-state folding lane gates flat."""
        key = '' if doc_id is None else self._doc_key(doc_id)
        n = lib().amtpu_op_count(self._pool, key.encode())
        if n < 0:
            _raise_last()
        return int(n)

    def drop_doc(self, doc_id):
        """Cold-doc eviction: removes the doc's entire state from the
        pool (checkpoint it FIRST -- `save()` -> disk; reload is
        `load()`).  Returns True if the doc existed."""
        key = self._doc_key(doc_id)
        found = lib().amtpu_drop_doc(self._pool, key.encode())
        if found < 0:
            _raise_last()
        self._storage.pop(key, None)
        return bool(found)

    def history_bytes(self, doc_id=None):
        """Retained raw-change bytes in the C++ arena (one doc, or the
        whole pool) -- the measure the storage gate bounds."""
        key = '' if doc_id is None else self._doc_key(doc_id)
        n = lib().amtpu_history_bytes(self._pool, key.encode())
        if n < 0:
            _raise_last()
        return int(n)

    #: amtpu_doc_stats columns, in ABI order (core.cpp has the
    #: authoritative comment); telemetry/capacity.py reads these names
    DOC_STAT_COLS = ('hist_bytes', 'ops', 'folded_ops', 'changes',
                     'queued', 'resclk_rows', 'clk_pairs',
                     'foldclk_bytes')

    def doc_stats(self):
        """Per-doc resource accounting in ONE C call for the whole pool
        (ISSUE 15): returns ``(doc_keys, stats)`` where `stats` is an
        int64 ndarray of shape (n_docs, len(DOC_STAT_COLS)) in the same
        first-seen doc order as `doc_keys`.  Column totals reconcile
        bit-exactly with `history_bytes()` / `op_count()` -- the
        capacity tests and `make capacity-check` pin it."""
        L = lib()
        n = int(L.amtpu_doc_count(self._pool))
        ncols = len(self.DOC_STAT_COLS)
        if n <= 0:
            return [], np.zeros((0, ncols), np.int64)
        buf = (ctypes.c_int64 * (n * ncols))()
        rows = L.amtpu_doc_stats(self._pool, buf, n * ncols)
        if rows < 0:
            _raise_last()
        ln = ctypes.c_int64()
        ptr = L.amtpu_doc_ids(self._pool, ctypes.byref(ln))
        if not ptr:
            _raise_last()
        ids = msgpack.unpackb(_take_buf(ptr, ln.value), raw=False)
        rows = int(rows)
        stats = np.frombuffer(buf, dtype=np.int64,
                              count=rows * ncols).reshape(rows, ncols)
        # a private copy: `buf` dies with this frame
        return ids[:rows], stats.copy()


class ShardedNativePool:
    """S independent native pools, driven pipelined or threaded.

    Document-level independence is the framework's data-parallel axis
    (SURVEY.md section 2); on the host it also shards the C++ runtime.
    Two drive modes (AMTPU_SHARD_MODE=pipeline|threads; default picks by
    core count):

    * pipeline -- single thread, async device dispatch: all shards run
      host `begin` + kernel dispatch first (phase a), then results are
      collected and emitted in order (phase b).  jax dispatches are
      async, so shard k's device work and d->h transfer overlap shard
      k+1's host begin and shard k-1's emit.  Strictly better on a
      1-core host, where extra threads only add contention.
    * threads -- one thread per shard; ctypes releases the GIL around
      native calls, so on multi-core hosts begin/emit of shards run
      truly concurrently on top of the same async device overlap.

    Doc -> shard routing uses the same FNV-1a hash as the C++ payload
    splitter.  API-compatible with NativeDocPool for apply_batch /
    apply_batch_bytes and the per-doc queries.

    Error semantics: shards commit independently; if one shard's batch
    fails, other shards may already have applied their sub-batches.  The
    first shard error is re-raised; callers needing atomicity must keep
    doc groups within one shard (route by doc id).
    """

    @staticmethod
    def resolve_mode(mode=None):
        cores = os.cpu_count() or 1
        if mode is None:
            mode = env_str('AMTPU_SHARD_MODE', '')
        if not mode:
            mode = 'pipeline' if cores == 1 else 'threads'
        if mode not in ('pipeline', 'threads'):
            raise ValueError('unknown shard mode %r' % (mode,))
        return mode

    @classmethod
    def default_shards(cls, mode=None):
        """Mode-aware shard-count default, without building any pools.

        Keys on the RESOLVED mode: pipelining overlaps async device work
        with host begin/emit, so more shards than cores helps (finer
        overlap granularity, smaller per-shard pads; 20 measured best on
        the 1-core headline bench, BASELINE.md round 3).  Threads mode
        runs shards truly concurrently, so one per core (capped) avoids
        oversubscription and unbounded per-shard state.

        Full host path (CPU backend, round 4): there is no device work
        to overlap, so the pipeline's extra shards are pure per-shard
        fixed cost -- ONE shard measured ~6% faster than 20 on the
        headline config (and skips the payload splitter entirely).
        """
        if _host_full_on():
            return 1
        mode = cls.resolve_mode(mode)
        return 20 if mode == 'pipeline' else min(8, os.cpu_count() or 1)

    def __init__(self, n_shards=None, mode=None):
        mode = self.resolve_mode(mode)
        self.mode = mode
        if n_shards is not None and n_shards < 1:
            raise ValueError('n_shards must be >= 1, got %r' % (n_shards,))
        # None = resolve lazily at first use: default_shards() keys on
        # _host_full_on(), which initializes the jax backend, and merely
        # CONSTRUCTING a pool must not take the chip (same lazy
        # convention as NativeDocPool._ensure_mode_flags)
        self._n_shards = n_shards        # guarded-by(w): self._pools_lock
        self._pools = None               # guarded-by(w): self._pools_lock
        # materialization lock: ANY entry point may be the first to touch
        # the lazy properties from concurrent threads; without it two
        # racers could each build a pool list and apply shards to pools
        # the losing assignment discards.  Reads stay lock-free (the
        # double-checked publish pattern: a reference load is atomic
        # under the GIL), so the guarded-by annotation covers WRITES --
        # `make static-check` enforces it (docs/ANALYSIS.md).
        import threading
        self._pools_lock = threading.Lock()

    @property
    def n_shards(self):
        if self._n_shards is None:
            with self._pools_lock:
                if self._n_shards is None:
                    self._n_shards = self.default_shards(self.mode)
        return self._n_shards

    @property
    def pools(self):
        # double-checked under the lock so every concurrent first-toucher
        # observes the SAME pool list (no call site needs to pre-touch)
        if self._pools is None:
            # resolve n_shards BEFORE taking the lock: it acquires the
            # same (non-reentrant) lock for its own lazy materialization
            n = self.n_shards
            with self._pools_lock:
                if self._pools is None:
                    self._pools = [NativeDocPool() for _ in range(n)]
        return self._pools

    def _shard_of(self, doc_id):
        key = NativeDocPool._doc_key(doc_id).encode()
        return int(lib().amtpu_doc_shard(key, len(key), self.n_shards))

    def apply_batch_bytes(self, payload):
        L = lib()
        t_batch = time.perf_counter()
        # warm the lazy pool list on THIS thread (the property itself is
        # now lock-guarded, so this is an optimization -- jax backend
        # resolution happens once here instead of inside a worker)
        self.pools
        with trace.span('shard.split'):
            sp = L.amtpu_shard_split(payload, len(payload), self.n_shards)
            if not sp:
                _raise_last()
        try:
            # zero-copy: shard sub-payloads stay in the C++ splitter's
            # buffers; begin() copies what it keeps, so the ShardSplit
            # only needs to outlive the begin calls (freed below)
            subs = []
            for s in range(self.n_shards):
                n = ctypes.c_int64()
                ptr = L.amtpu_shard_buf(sp, s, ctypes.byref(n))
                subs.append((ctypes.cast(ptr, ctypes.c_char_p), n.value)
                            if n.value > 1 else None)
            with trace.span('shard.run'):
                results, errors = self._run(subs)
            if errors:
                # poison-batch isolation at SHARD granularity: a failed
                # shard rolled its pool back, so its whole sub-payload
                # re-applies through the resilience layer (retry ->
                # bisect -> quarantine) while the healthy shards'
                # results stand (docs/RESILIENCE.md)
                errors = self._retry_failed_shards(subs, results, errors)
            _raise_shard_errors(errors)
        finally:
            L.amtpu_shard_free(sp)
        # merge the per-shard {doc: patch} maps at the byte level: sum the
        # map headers, splice the bodies -- no decode of patch contents
        total = 0
        bodies = []
        for r in results:
            if r is None:
                continue
            n, off = _read_map_header(r)
            total += n
            bodies.append(memoryview(r)[off:])   # no intermediate copy
        out = _map_header(total) + b''.join(bodies)
        # whole-batch series; shard sub-batches land under pool="native"
        # (threads mode) or not at all (pipeline mode drives _phase_a/b
        # directly), so the two label values never double-count one level
        telemetry.observe_batch(self._batch_label,
                                time.perf_counter() - t_batch,
                                docs=_read_map_header(payload)[0])
        return out

    #: batch-latency series label (`MeshDocPool` overrides with 'mesh'
    #: so its lines are attributable; `telemetry.collect_share` knows
    #: every value)
    _batch_label = 'sharded'

    def _run(self, subs):
        """Drive-mode dispatch for one split payload; subclasses (the
        mesh pool) override with their own drive."""
        if self.mode == 'pipeline':
            return self._run_pipelined(subs)
        return self._run_threaded(subs)

    def _run_pipelined(self, subs):
        """Phase a for every shard, then phase b READY-FIRST: shards
        whose device outputs already resolved collect and emit before a
        slow shard that happens to sit earlier in submission order
        (_collect_ready_order).  A shard error must NOT leave *other*
        shards half-applied (their begin has already committed state),
        so every healthy shard still runs to completion and the first
        error is re-raised afterwards -- matching the threads-mode
        semantics."""
        ctxs = []
        results = [None] * self.n_shards
        errors = []
        for s in range(self.n_shards):
            if subs[s] is None:
                continue
            try:
                ctxs.append((s, self.pools[s], self.pools[s]._phase_a(
                    subs[s])))
            except Exception as e:
                errors.append((s, e))

        def keep(s, result):
            results[s] = result

        _collect_ready_order(ctxs, on_result=keep,
                             on_error=lambda s, e: errors.append((s, e)))
        return results, errors

    def _run_threaded(self, subs):
        results = [None] * self.n_shards
        errors = []

        def run(s):
            try:
                if subs[s] is not None:
                    results[s] = self.pools[s].apply_batch_bytes(subs[s])
            except Exception as e:         # re-raised on the caller thread
                errors.append((s, e))

        import threading
        threads = [threading.Thread(target=run, args=(s,))
                   for s in range(self.n_shards)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return results, errors

    def _retry_failed_shards(self, subs, results, errors):
        """Re-applies each failed shard's sub-payload through the
        resilience layer on that shard's own pool; returns the errors
        resilience must not isolate (they re-raise, as before)."""
        from .. import resilience
        remaining = []
        for s, e in errors:
            if not resilience.should_isolate(e):
                remaining.append((s, e))
                continue
            try:
                results[s] = resilience.apply_payload(
                    self.pools[s], subs[s], first_exc=e)
            except Exception as e2:
                remaining.append((s, e2))
        return remaining

    def apply_batch_bytes_resilient(self, payload):
        """Alias for `apply_batch_bytes`: the sharded driver already
        isolates failures per shard internally."""
        return self.apply_batch_bytes(payload)

    def apply_batch(self, changes_by_doc):
        return _apply_batch_dicts(self, changes_by_doc)

    def apply_changes(self, doc_id, changes):
        return self.pools[self._shard_of(doc_id)].apply_changes(
            doc_id, changes)   # quarantine raises inside (single doc)

    def apply_local_change(self, doc_id, request):
        return self.pools[self._shard_of(doc_id)].apply_local_change(
            doc_id, request)

    def get_patch(self, doc_id):
        return self.pools[self._shard_of(doc_id)].get_patch(doc_id)

    def get_clock(self, doc_id):
        return self.pools[self._shard_of(doc_id)].get_clock(doc_id)

    def save(self, doc_id):
        return self.pools[self._shard_of(doc_id)].save(doc_id)

    def load(self, doc_id, data):
        return self.pools[self._shard_of(doc_id)].load(doc_id, data)

    def load_batch(self, blobs):
        """One batched replay for many checkpoints (the payload splitter
        routes docs to their shards)."""
        _load_batch(self, blobs)

    def restore_from_store(self, store, doc_ids=None, batch=None,
                           threads=None):
        """Parallel per-shard restore off the store's durable manifest:
        each shard's doc group decodes + applies on its own thread with
        the GIL released (module-level `restore_from_store`)."""
        return restore_from_store(self, store, doc_ids=doc_ids,
                                  batch=batch, threads=threads)

    def get_missing_deps(self, doc_id):
        return self.pools[self._shard_of(doc_id)].get_missing_deps(doc_id)

    def get_missing_changes(self, doc_id, have_deps):
        return self.pools[self._shard_of(doc_id)].get_missing_changes(
            doc_id, have_deps)

    def get_register(self, doc_id, obj, key):
        return self.pools[self._shard_of(doc_id)].get_register(
            doc_id, obj, key)

    def get_changes_for_actor(self, doc_id, actor, after_seq=0):
        return self.pools[self._shard_of(doc_id)].get_changes_for_actor(
            doc_id, actor, after_seq)

    def get_changes_for_actor_bytes(self, doc_id, actor, after_seq=0):
        return self.pools[self._shard_of(doc_id)] \
            .get_changes_for_actor_bytes(doc_id, actor, after_seq)

    def compact(self, doc_id, frontier=None, min_changes=0):
        return self.pools[self._shard_of(doc_id)].compact(
            doc_id, frontier, min_changes)

    def drop_doc(self, doc_id):
        return self.pools[self._shard_of(doc_id)].drop_doc(doc_id)

    def history_bytes(self, doc_id=None):
        if doc_id is not None:
            return self.pools[self._shard_of(doc_id)] \
                .history_bytes(doc_id)
        return sum(p.history_bytes() for p in self.pools)

    def op_count(self, doc_id=None):
        if doc_id is not None:
            return self.pools[self._shard_of(doc_id)].op_count(doc_id)
        return sum(p.op_count() for p in self.pools)

    def clock_pairs(self, doc_id=None):
        if doc_id is not None:
            return self.pools[self._shard_of(doc_id)].clock_pairs(doc_id)
        return sum(p.clock_pairs() for p in self.pools)

    def resclk_row_bytes(self):
        """Widest shard's row cost: shards serve one doc population, so
        actor widths track each other -- the capacity tier wants a
        stable per-row conversion, not per-shard precision."""
        return max(p.resclk_row_bytes() for p in self.pools)

    DOC_STAT_COLS = NativeDocPool.DOC_STAT_COLS

    def doc_stats(self):
        """Per-doc stats across every shard (one C call per shard),
        concatenated in shard order -- same (doc_keys, (N, cols) int64
        ndarray) contract as `NativeDocPool.doc_stats`."""
        ids, mats = [], []
        for p in self.pools:
            pids, pstats = p.doc_stats()
            ids.extend(pids)
            if len(pids):
                mats.append(pstats)
        if not mats:
            return ids, np.zeros((0, len(self.DOC_STAT_COLS)), np.int64)
        return ids, np.concatenate(mats, axis=0)


_compile_watch_lock = threading.Lock()
_compile_watched = False


def _watch_compiles():
    """Counts every backend compile in this process, once registered:
    `jit.compiles`, `jit.compile_s` and `jit.compiles.<fun_name>`, from
    JAX's own compile-duration event (a persistent-cache read fires it
    too).  Registered once, by the first `make_pool()`."""
    global _compile_watched
    with _compile_watch_lock:
        if _compile_watched:
            return
        _compile_watched = True
    import jax

    def on_duration(event, secs, fun_name=None, **_):
        if event != '/jax/core/compile/backend_compile_duration':
            return
        telemetry.metric('jit.compiles')
        telemetry.metric('jit.compile_s', secs)
        telemetry.metric('jit.compiles.%s' % fun_name)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _count_devices():
    """(platform, device kind, count) of the devices this process holds;
    starts JAX's backend if nothing has yet."""
    import jax
    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


def _devices_held():
    """`_count_devices()`, or None where counting would start a backend
    that may take a TPU: none is up yet and JAX may use more than the
    CPU.  A process that has not touched JAX keeps its chips free."""
    import jax
    from jax._src import xla_bridge
    if (not xla_bridge.backends_are_initialized()
            and jax.config.jax_platforms != 'cpu'):
        return None
    return _count_devices()


def _layout_for(held):
    """dp of the pool for `held` devices: every chip of a multi-chip TPU
    process, else 1 (one chip, or a CPU backend whatever its virtual
    devices: the CPU runs the full host path, where chips buy nothing)."""
    platform, _kind, count = held
    return count if platform == 'tpu' and count > 1 else 1


_layouts_stated = set()


def _state_layout(pool_class, dp, held):
    """Says once per process and layout, on stderr, which pool serves
    and on what: pool class, dp, device kind and count; adds dp to the
    ``pool.chips`` counter."""
    if (pool_class, dp) in _layouts_stated:
        return
    _layouts_stated.add((pool_class, dp))
    telemetry.metric('pool.chips', dp)
    platform, kind, count = held
    print('[pool] %s dp=%d on %d x %s (%s)'
          % (pool_class, dp, count, kind, platform),
          file=sys.stderr, flush=True)


def _pool_for(held):
    dp = _layout_for(held)
    if dp == 1:
        _state_layout('NativeDocPool', 1, held)
        return NativeDocPool()
    from .mesh_pool import MeshDocPool
    return MeshDocPool(dp=dp)      # states its layout at first use


class _PoolAtFirstUse(object):
    """`make_pool`'s pool in a process with no JAX backend up yet: the
    chips are counted, and the pool built, at the first attribute
    anything reads or sets; from then on every attribute is that
    pool's."""

    def __init__(self):
        object.__setattr__(self, '_built', None)
        object.__setattr__(self, '_build_lock', threading.Lock())

    def _pool_now(self):
        with self._build_lock:
            if self._built is None:
                object.__setattr__(self, '_built',
                                   _pool_for(_count_devices()))
        return self._built

    def __getattr__(self, name):
        if name in ('_built', '_build_lock'):   # a copy made without init
            raise AttributeError(name)
        return getattr(self._pool_now(), name)

    def __setattr__(self, name, value):
        setattr(self._pool_now(), name, value)


def make_pool():
    """The pool for the chips this process holds: `MeshDocPool(dp=n)`
    on a TPU process with n > 1 chips (docs split by the FNV doc hash,
    one chip pool and host thread per chip), else a plain
    `NativeDocPool`.  In a process that has not started JAX yet the
    chips are counted at the pool's first use, so building a pool never
    takes the chips.  ``AMTPU_MESH=dp[,sp]`` overrides the layout the
    chips decide (``0`` forces the single pool); it is kept as a test
    seam.  The sidecar backend, the gateway's benchmark and the CI
    gates construct through this."""
    _watch_compiles()
    if env_raw('AMTPU_MESH') is not None:
        mesh = parse_mesh_env()
        if mesh is None:
            return NativeDocPool()
        from .mesh_pool import MeshDocPool
        return MeshDocPool(dp=mesh[0], sp=mesh[1])
    held = _devices_held()
    if held is None:
        return _PoolAtFirstUse()
    return _pool_for(held)


def __getattr__(name):
    # lazy so importing the native driver never drags the mesh module
    # (and through it jax device enumeration) into processes that only
    # serve single-device traffic
    if name in ('MeshDocPool', 'MeshChipPool'):
        from . import mesh_pool
        return getattr(mesh_pool, name)
    raise AttributeError('module %r has no attribute %r'
                         % (__name__, name))
