"""The system under test, in this process: the native library as make
builds it, and the program's gateway (msgpack framing) on a unix socket
over a pool from `native.make_pool()`.

`FAULTS` plant the failures that the check must catch, under the pool's
`apply_batch`, which every flush of the gateway calls.  Only the
benchmark's own tests and the control runs use them.
"""

import os
import subprocess
import sys

from .spec import HERE

ROOT = os.path.dirname(HERE)


def build_native():
    """Runs make in native/ by its own rules (a fresh library is left
    alone); make's output goes to stderr."""
    rc = subprocess.call(['make', '-C', os.path.join(ROOT, 'native')],
                         stdout=sys.stderr, stderr=sys.stderr)
    if rc != 0:
        raise RuntimeError('make in native/ exited %d' % rc)


def _drop(changes_by_doc, keep):
    return {d: (changes_by_doc[d] if keep(n, d) else [])
            for n, d in enumerate(sorted(changes_by_doc))}


def _lose_writes(apply, share):
    """Acknowledges one doc in `share` of each flush without applying its
    changes (which docs moves from flush to flush): it breaks the
    guarantee that an acknowledged change is visible."""
    flushes = [0]

    def run(changes_by_doc):
        flushes[0] += 1
        return apply(_drop(changes_by_doc,
                           lambda n, d: (n + flushes[0]) % share))
    return run


def _stale(apply):
    """A step that leaves the state as it was and answers anyway."""
    def run(changes_by_doc):
        return apply({d: [] for d in changes_by_doc})
    return run


def _half(apply):
    """Half of each flush's docs left out of the batch."""
    def run(changes_by_doc):
        half = (len(changes_by_doc) + 1) // 2
        return apply(_drop(changes_by_doc, lambda n, d: n < half))
    return run


def _altered(apply):
    """Every answer altered where it is produced: in each doc's patch the
    first diff that carries a value gets another one."""
    def run(changes_by_doc):
        out = apply(changes_by_doc)
        for res in out.values():
            for diff in res.get('diffs') or ():
                if 'value' in diff:
                    diff['value'] = ['altered', diff['value']]
                    break
        return out
    return run


FAULTS = {'control': lambda apply: _lose_writes(apply, 16),
          'stale': _stale, 'half': _half, 'altered': _altered}


class Gateway(object):
    """The in-process gateway and its pool; `stop()` frees both."""

    def __init__(self, sock_path, queue_max_ops=None, fault=None):
        from automerge_tpu.native import make_pool
        from automerge_tpu.scheduler import GatewayServer
        from automerge_tpu.scheduler.queue import AdmissionQueue
        from automerge_tpu.sidecar.server import SidecarBackend
        self.sock_path = sock_path
        pool = make_pool()
        if fault is not None:
            pool.apply_batch = FAULTS[fault](pool.apply_batch)
        queue = AdmissionQueue(max_ops=queue_max_ops) \
            if queue_max_ops else None
        self.server = GatewayServer(sock_path, use_msgpack=True,
                                    backend=SidecarBackend(pool=pool),
                                    queue=queue).start()

    def stop(self):
        if self.server is not None:
            self.server.stop()
            self.server = None
