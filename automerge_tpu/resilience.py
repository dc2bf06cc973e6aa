"""Poison-batch isolation + graceful degradation (docs/RESILIENCE.md).

One malformed change or one failing native call used to take down an
entire multi-thousand-doc batch.  This module turns such a failure
inside ``NativeDocPool.apply_batch`` / ``ShardedNativePool`` into the
smallest possible blast radius:

  1. **retry** -- transient failures (``faults.is_transient``) get
     bounded retries with exponential backoff
     (``resilience.retry.*`` counters);
  2. **bisect** -- a failure that persists splits the doc set in half
     and re-applies each half independently, converging on the poison
     doc(s) in O(log n) extra applies (``resilience.bisect.rounds``);
  3. **quarantine / degrade** -- a poisoned singleton either degrades to
     the full-host path (``AMTPU_DEGRADE=1``; no device work at all;
     ``resilience.degraded`` -- deliberately distinct from
     ``fallback.oracle`` so the perf gates stay meaningful) or is
     quarantined: its slot in the batch result carries the protocol's
     per-doc error envelope ``{'error': ..., 'errorType': ...}`` while
     every healthy doc's patch commits normally
     (``resilience.quarantined``).

All of this is only byte-safe because a failed batch now ROLLS BACK:
`native.amtpu_batch_rollback` restores the pool to its pre-begin state
on any pre-emit failure, so re-applying the same changes is not
swallowed by seq dedup.  An exception marked ``amtpu_state_suspect``
(emit already ran; rollback impossible) is never retried or bisected --
it re-raises like the pre-resilience code.

Protocol-level errors (`AutomergeError`, `RangeError`, `TypeError`,
`KeyError` -- validation, not infrastructure) never START isolation:
on a batch whose only problem is validation they re-raise whole-batch
exactly as before, so error-contract tests and callers keep their
semantics.  Once isolation HAS begun (an infrastructure fault fired
first), sibling groups may already have committed, so even validation
errors then resolve per doc -- their envelope carries the real
errorType -- rather than falsely claiming "nothing applied".

``AMTPU_RESILIENCE=0`` disables the whole layer (failures re-raise,
post-rollback).
"""

import time

import msgpack
from jax.errors import JaxRuntimeError

from . import faults, telemetry
from .errors import AutomergeError
from .telemetry import recorder
from .utils.common import env_bool, env_float, env_int
from .utils.wire import map_header as _map_header
from .utils.wire import read_map_header as _read_map_header


def enabled():
    return env_bool('AMTPU_RESILIENCE', True)


def _retry_max():
    return env_int('AMTPU_RETRY_MAX', 3)


def _backoff_base_s():
    return env_float('AMTPU_RETRY_BACKOFF_S', 0.05)


#: exponential backoff ceiling -- a persistent fault should not turn
#: one batch into a minutes-long retry stall
_BACKOFF_CAP_S = 1.0


def _degrade_on():
    return env_bool('AMTPU_DEGRADE', False)


def should_isolate(exc):
    """Whether the resilience machinery may handle ``exc`` at all.

    Injected faults always qualify.  Real-world infrastructure failures
    (RuntimeError, OSError, MemoryError/SystemError for allocator or
    interpreter trouble) qualify unless the batch is state-suspect.  A
    failing device does not (`JaxRuntimeError`): it is no one doc's
    poison, and retrying, quarantining or degrading to the host path
    around it would hide it -- the batch raises.  Protocol validation
    errors never do either -- the whole-batch raise IS their contract.
    """
    if not enabled():
        return False
    if getattr(exc, 'amtpu_state_suspect', False):
        return False
    if isinstance(exc, faults.InjectedFault):
        return True
    if isinstance(exc, (AutomergeError, TypeError, KeyError,
                        JaxRuntimeError)):
        return False
    return isinstance(exc, (RuntimeError, OSError, MemoryError,
                            SystemError))


def error_envelope(exc):
    """The protocol's per-doc error envelope for a quarantined doc --
    the same ``error``/``errorType`` shape the sidecar answers for
    whole-request failures, embedded as that doc's result value."""
    return {'error': str(exc) or type(exc).__name__,
            'errorType': type(exc).__name__}


def is_quarantined(result):
    """True when a per-doc batch result is an error envelope rather
    than a patch (the caller-facing test for quarantine)."""
    return isinstance(result, dict) and 'errorType' in result \
        and 'error' in result and 'clock' not in result


#: the message shape `native._raise_if_quarantined` uses when a
#: SINGLE-doc entry point surfaces a quarantine envelope as its raise
#: contract -- defined here (the quarantine authority) so consumers
#: recognizing that surface (the gateway's fan-out, which owes
#: subscribers the envelope even when the doc was mutated through a
#: singleton path) share one contract with the raiser
QUARANTINE_RAISE_MARKER = ' quarantined: ['


def is_quarantine_error(resp):
    """True when a protocol error response is the single-doc surface of
    a quarantine (`_raise_if_quarantined`) rather than a validation
    error -- the fan-out test for 'envelope, not silence' on the
    exec/serial-fallback path."""
    return isinstance(resp, dict) \
        and resp.get('errorType') == 'AutomergeError' \
        and QUARANTINE_RAISE_MARKER in str(resp.get('error', ''))


def apply_payload(pool, payload, first_exc=None):
    """``apply_batch_bytes`` with retry/bisect/quarantine semantics.

    Returns result BYTES byte-compatible with ``apply_batch_bytes``
    output (msgpack ``{doc_key: patch}``), with quarantined docs mapped
    to their error envelope instead of a patch.  Exceptions the layer
    must not isolate re-raise unchanged.

    ``first_exc`` carries a failure the caller already observed (the
    sharded driver retries a failed shard's sub-payload here without
    paying a doomed extra attempt).
    """
    if first_exc is None:
        try:
            return pool.apply_batch_bytes(payload)
        except Exception as e:
            if not should_isolate(e):
                if getattr(e, 'amtpu_state_suspect', False):
                    recorder.record('resilience.state_suspect',
                                    detail=type(e).__name__)
                    recorder.dump('state_suspect')
                raise
            first_exc = e
    if isinstance(payload, tuple):   # zero-copy shard view: materialize
        import ctypes
        payload = ctypes.string_at(payload[0], payload[1])
    keyed = msgpack.unpackb(payload, raw=False, strict_map_key=False)
    # results merge at the BYTE level (sum the map headers, splice the
    # bodies -- the same trick as the sharded merge): every surviving
    # doc's patch bytes stay exactly as the C++ emit produced them, so
    # a retry-recovered batch is byte-identical to the fault-free run
    parts = []                       # (n_docs, body_bytes)
    _apply_group(pool, keyed, list(keyed), parts, pending_exc=first_exc)
    total = sum(n for n, _ in parts)
    return _map_header(total) + b''.join(b for _, b in parts)


def _append_raw(parts, raw):
    n, off = _read_map_header(raw)
    parts.append((n, memoryview(raw)[off:]))


def _apply_group(pool, keyed, doc_list, parts, pending_exc=None):
    """Recursive retry/bisect driver over one doc subset.  Healthy docs'
    raw patch bytes land in ``parts``; poisoned docs land as packed
    error envelopes."""
    delay = _backoff_base_s()
    attempts_left = _retry_max()
    retried = False
    exc = pending_exc
    sub = None          # built once; retries re-send the same bytes
    while True:
        if exc is None:
            try:
                if sub is None:
                    sub = msgpack.packb({k: keyed[k] for k in doc_list},
                                        use_bin_type=True)
                _append_raw(parts, pool.apply_batch_bytes(sub))
                if retried:
                    telemetry.metric('resilience.retry.success')
                return
            except Exception as e:
                # Isolation has already begun: sibling groups may have
                # committed, so re-raising here would claim "nothing
                # applied" while half the batch stands.  Even protocol
                # errors therefore resolve per doc inside this pass
                # (their envelope carries the real errorType); only a
                # state-suspect failure still re-raises -- re-applying
                # those docs is unsafe in any form.
                if getattr(e, 'amtpu_state_suspect', False):
                    recorder.record('resilience.state_suspect',
                                    n=len(doc_list),
                                    detail=type(e).__name__)
                    recorder.dump('state_suspect')
                    raise
                exc = e
        if faults.is_transient(exc) and attempts_left > 0:
            attempts_left -= 1
            retried = True
            telemetry.metric('resilience.retry.attempts')
            recorder.record('resilience.retry', n=len(doc_list),
                            detail=type(exc).__name__)
            time.sleep(delay)
            delay = min(delay * 2, _BACKOFF_CAP_S)
            exc = None
            continue
        break
    if faults.is_transient(exc):
        telemetry.metric('resilience.retry.exhausted')
    if len(doc_list) > 1:
        telemetry.metric('resilience.bisect.rounds')
        recorder.record('resilience.bisect', n=len(doc_list))
        mid = len(doc_list) // 2
        _apply_group(pool, keyed, doc_list[:mid], parts)
        _apply_group(pool, keyed, doc_list[mid:], parts)
        return
    key = doc_list[0]
    if _degrade_on():
        try:
            _append_raw(parts, _apply_degraded(pool, key, keyed[key]))
            telemetry.metric('resilience.degraded')
            telemetry.note_degraded()
            return
        except Exception as e:
            if getattr(e, 'amtpu_state_suspect', False):
                raise
            exc = e
    telemetry.metric('resilience.quarantined')
    telemetry.note_degraded()
    # the quarantine IS the post-mortem moment: stamp the event and
    # dump the surrounding ring (docs/RESILIENCE.md; rate-limited so a
    # poison-storm cannot become a disk-write storm)
    recorder.record('resilience.quarantine', doc=key,
                    detail=type(exc).__name__)
    recorder.dump('quarantine')
    parts.append((1, msgpack.packb(key, use_bin_type=True) +
                  msgpack.packb(error_envelope(exc), use_bin_type=True)))


def _apply_degraded(pool, key, changes):
    """Applies one poisoned doc on the FULL HOST path: the C++ pool
    resolves registers and list indexes itself with zero device
    dispatches.  A device failure never gets here (`should_isolate`
    refuses it).  Returns the
    raw result bytes.  Counted as ``resilience.degraded`` -- NOT
    ``fallback.oracle``, which gates the healthy kernel path's
    escalation ladder."""
    from .native import _host_full_on, lib
    base = pool
    if hasattr(pool, '_shard_of'):       # route to the doc's shard pool
        base = pool.pools[pool._shard_of(key)]
    handle = getattr(base, '_pool', None)
    if handle is None:
        raise RuntimeError('degraded path needs a native pool')
    sub = msgpack.packb({key: changes}, use_bin_type=True)
    L = lib()
    L.amtpu_pool_set_hostfull(handle, 1)
    try:
        return base.apply_batch_bytes(sub)
    finally:
        L.amtpu_pool_set_hostfull(handle, 1 if _host_full_on() else 0)
