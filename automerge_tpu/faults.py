"""Fault injection registry (docs/RESILIENCE.md).

The reference library scripts a network fault model for its connection
tests (mirrored by the ``drop`` hook in `sync/replica_set.py`); this
module extends that philosophy to the layers the reference never had:
device dispatch, the native C++ pool, and the sidecar process boundary.
Named injection SITES are threaded through the hot paths; arming a site
makes the next matching pass raise a typed fault exactly where a real
XLA/device/runtime error would surface, so the resilience machinery
(`automerge_tpu.resilience`, the self-healing sidecar client) can be
driven deterministically in tests and chaos smokes.

Sites (see docs/RESILIENCE.md for what each models):

  native.begin      C++ decode/schedule/encode (amtpu_begin succeeded,
                    fault fires before any dispatch)
  device.dispatch   JAX kernel dispatch (phase a; kernel path only)
  device.collect    device->host result collection (phase b, pre-mid)
  native.mid        C++ mid phase (fires before any amtpu_mid* call)
  escalation.tier   wider-window escalation tier dispatch
  sidecar.frame     sidecar server request framing (uncaught by design:
                    the serve loop dies, simulating a process crash)
  checkpoint.load   save()-checkpoint restore (WAL replay path)
  fanout.write      per-connection egress write failure (the writer
                    thread treats it as a dead transport and tears the
                    connection down off the flush critical path)
  fanout.stall      armed wedge: the egress writer makes no progress
                    while it fires, so a permanent stall drives the
                    AMTPU_EGRESS_WEDGE_S tier-3 eviction
                    deterministically
  storage.save      cold-store blob write, mid-stream (a partial
                    tempfile exists, the atomic rename has not run --
                    models a kill mid-save; the prior committed copy
                    and the durable manifest must survive)
  router.forward    router -> replica raw-frame forward (the data
                    path); the router answers the retryable
                    ReplicaUnavailable envelope, exactly as a dead
                    upstream socket would
  router.heartbeat  router health-monitor probe; `docs` carries the
                    probed member id so `match` pins the fault to one
                    replica -- a permanent spec drives the
                    up -> suspect -> dead -> failover ladder
                    deterministically, a counted transient spec clears
                    as a recovery

Arming:

  * environment -- ``AMTPU_FAULT=site:kind:prob[:count]`` where kind is
    ``transient`` | ``permanent``, prob in [0, 1], count bounds total
    fires (omitted = unlimited).  Multiple comma-separated specs
    compose.  Parsed at import, so armed specs propagate into sidecar
    server subprocesses through the environment.
  * programmatic -- ``faults.arm(site, kind, prob, count=..., match=...)``;
    ``match`` pins the fault to batches containing a doc key with that
    substring (poison-doc simulation; env specs cannot pin).

Cost model: disarmed, the hot paths pay ONE module-attribute read per
site (``if faults.ARMED:`` -- the same shim pattern as ``trace.ENABLED``);
no call, no dict lookup.  ``make perf-smoke`` / ``make fallback-check``
run with the hooks in place and gate that the fast paths are unchanged.
"""

import random
import threading

from . import telemetry
from .utils.common import env_raw, env_str

#: the site universe -- arm() rejects anything else so a typo'd env spec
#: fails loudly instead of never firing
SITES = ('native.begin', 'native.mid', 'device.dispatch',
         'device.collect', 'escalation.tier', 'sidecar.frame',
         'checkpoint.load', 'fanout.write', 'fanout.stall',
         'storage.save', 'router.forward', 'router.heartbeat')

KINDS = ('transient', 'permanent')

#: fast gate: True iff any spec is armed.  Hot paths read this ONE
#: attribute and skip everything else when False.
ARMED = False


class InjectedFault(Exception):
    """Base of the injected fault types; carries its site and kind."""

    kind = 'permanent'

    def __init__(self, site, detail=''):
        self.site = site
        super().__init__('injected %s fault at %s%s'
                         % (self.kind, site,
                            ' (%s)' % detail if detail else ''))


class TransientFault(InjectedFault):
    """A fault that models a retryable condition (device hiccup,
    preemption, transient allocator pressure): bounded retries with
    backoff are expected to clear it."""

    kind = 'transient'


class PermanentFault(InjectedFault):
    """A fault that models a deterministic failure (a poison doc):
    retries never clear it; isolation/quarantine must."""

    kind = 'permanent'


class _Spec:
    __slots__ = ('site', 'kind', 'prob', 'count', 'match')

    def __init__(self, site, kind, prob, count, match):
        self.site = site
        self.kind = kind
        self.prob = prob
        self.count = count       # remaining fires; None = unlimited
        self.match = match       # doc-key substring pin; None = any


_lock = threading.Lock()
_specs = []
# deterministic across a test lane when seeded (AMTPU_FAULT_SEED)
_rng = random.Random()


def _refresh_armed():
    global ARMED
    ARMED = bool(_specs)


def arm(site, kind='transient', prob=1.0, count=None, match=None):
    """Arms one fault spec; returns it (pass to :func:`disarm`)."""
    if site not in SITES:
        raise ValueError('unknown fault site %r (one of %s)'
                         % (site, ', '.join(SITES)))
    if kind not in KINDS:
        raise ValueError('unknown fault kind %r (transient|permanent)'
                         % (kind,))
    prob = float(prob)
    if not 0.0 <= prob <= 1.0:
        raise ValueError('fault probability %r outside [0, 1]' % (prob,))
    if count is not None and int(count) < 1:
        raise ValueError('fault count must be >= 1, got %r' % (count,))
    spec = _Spec(site, kind, prob,
                 None if count is None else int(count), match)
    with _lock:
        _specs.append(spec)
        _refresh_armed()
    return spec


def disarm(spec=None):
    """Removes one spec, or every spec when called without arguments."""
    with _lock:
        if spec is None:
            del _specs[:]
        else:
            try:
                _specs.remove(spec)
            except ValueError:
                pass
        _refresh_armed()


def reset(env=None):
    """Test isolation: drop every armed spec, then re-arm from the
    environment (``env`` overrides ``os.environ['AMTPU_FAULT']``)."""
    disarm()
    load_env(env)


def load_env(value=None):
    """Parses ``AMTPU_FAULT=site:kind:prob[:count][,spec...]`` and arms
    each spec.  A malformed spec raises (a chaos run with a typo'd fault
    must not silently test nothing)."""
    if value is None:
        value = env_str('AMTPU_FAULT', '')
    seed = env_raw('AMTPU_FAULT_SEED')
    if seed:
        _rng.seed(seed)
    for part in filter(None, (p.strip() for p in value.split(','))):
        bits = part.split(':')
        if len(bits) not in (3, 4):
            raise ValueError(
                'bad AMTPU_FAULT spec %r (want site:kind:prob[:count])'
                % (part,))
        arm(bits[0], bits[1], float(bits[2]),
            count=int(bits[3]) if len(bits) == 4 else None)


def fire(site, docs=None):
    """Raises a typed fault when an armed spec matches this pass.

    ``docs`` is the batch's doc-key list when the site has one (None
    where no doc scope exists, e.g. sidecar framing); a spec armed with
    ``match`` only fires when some doc key contains the pin, so
    bisection converges on exactly the poisoned doc(s).

    Only called behind the ``faults.ARMED`` gate -- never on the
    disarmed fast path.
    """
    with _lock:
        for spec in _specs:
            if spec.site != site:
                continue
            if spec.match is not None:
                if docs is None or not any(spec.match in d for d in docs):
                    continue
            if spec.prob < 1.0 and _rng.random() >= spec.prob:
                continue
            if spec.count is not None:
                spec.count -= 1
                if spec.count <= 0:
                    _specs.remove(spec)
                    _refresh_armed()
            kind = spec.kind
            break
        else:
            return
    telemetry.metric('resilience.fault_injected')
    telemetry.metric('resilience.fault_injected.' + site)
    telemetry.recorder.record('fault.injected', n=1,
                              doc=spec.match, detail='%s:%s'
                              % (site, kind))
    cls = TransientFault if kind == 'transient' else PermanentFault
    detail = spec.match if spec.match is not None else ''
    raise cls(site, detail)


def is_transient(exc):
    """Whether bounded retries are worth attempting for ``exc``.

    Injected faults declare themselves; real-world classification keeps
    a deliberately narrow allowlist -- OS-level hiccups and the XLA
    status codes that name retryable conditions.  Everything else (and
    every :class:`PermanentFault`) is permanent: retrying a
    deterministic failure just triples its latency.
    """
    if isinstance(exc, TransientFault):
        return True
    if isinstance(exc, InjectedFault):
        return False
    if isinstance(exc, (BrokenPipeError, ConnectionError, InterruptedError,
                        TimeoutError)):
        return True
    if type(exc).__name__ == 'XlaRuntimeError':
        msg = str(exc).upper()
        return any(code in msg for code in
                   ('RESOURCE_EXHAUSTED', 'UNAVAILABLE', 'ABORTED',
                    'DEADLINE_EXCEEDED', 'CANCELLED'))
    return False


# armed specs must propagate into subprocesses (the sidecar server, the
# bench/check subprocess drivers) without every entry point re-parsing
load_env()
