"""Closed-loop catch-up: one sync peer relays a stream of document
backlogs.

The docs are cut into slots of ``docs / slots_per_backlog`` docs, and a
doc's history into ``rounds_per_doc`` rounds.  Request `k` brings slot
`k` its round 1, slot `k - 1` its round 2, and so on: new docs arrive
while the docs before them catch up, each doc's rounds one request apart.
From request ``rounds_per_doc - 1`` on, every request holds the same
work, and no doc grows past its last round, so the window measures a
steady state whatever its length or the program's speed.

One connection sends the `apply_batch` requests, keeping ``depth``
outstanding: one runs while the next waits.  Set-up sends the first
``warm_requests`` of them the same way and waits for them; the window
goes on with the next.  Generator processes make the requests ahead of
need, each a pure function of (seed, request index): ``lookahead`` of
them are made before the window opens and none inside it unless those
run out (the relay reports how long it then waited), so that in the
window the load comes from the relay alone.

The window sends requests until its time is up, then sends nothing
more and waits for every request it sent; its time runs from its
opening to the clock read after that wait, and its work is the ops of
every request it sent and had acknowledged.  The check takes
``sample_per_slot`` docs of every slot the window touched, drawn from
the seed, replays their rounds through the reference, and compares
every patch the gateway returned for them, then the state read back
through `get_patch` once the last answer is in.
"""

import collections
import random
import sys
import time

from harness import check, children, roofline, spec, wire

DRAIN_S = 60.0


def _slot_docs(cfg, mix, s):
    """Doc indexes of slot `s`."""
    n = cfg['docs'] // mix['slots_per_backlog']
    return range(s * n, (s + 1) * n)


def _parts(mix, k):
    """(slot, round) pairs of request `k`."""
    return [(k - h, h + 1) for h in range(mix['rounds_per_doc'])
            if k - h >= 0]


def _sampled(cfg, mix, seed, s):
    docs = _slot_docs(cfg, mix, s)
    return random.Random('sample:%d:%d' % (seed, s)).sample(
        docs, min(mix['sample_per_slot'], len(docs)))


def _request(args):
    """Request `k` as wire bytes, with its op count by action; runs in a
    generator process."""
    cfg, mix, seed, k = args
    dt = spec.doctype(cfg)
    docs = {}
    counts = {}
    for s, r in _parts(mix, k):
        for i in _slot_docs(cfg, mix, s):
            chs = dt.round_changes(cfg, seed, i, r)
            docs[dt.doc_id(i)] = chs
            roofline.count_ops(chs, counts)
    body = wire.frame({'cmd': 'apply_batch', 'id': k + 1, 'docs': docs})
    return k, body, counts


class _Ahead(object):
    """Requests made by a pool of generator processes, `lookahead` ahead
    of the one the relay sends next until `prefetch`; after it one is
    made only when none is left."""

    def __init__(self, cfg, mix, seed):
        self.args = (cfg, mix, seed)
        self.pool = children.context().Pool(mix['generators'])
        self.pending = collections.deque()
        self.next_k = 0
        self.taken = 0
        self.lookahead = mix['lookahead']
        self.wait_s = 0.0
        self.late = 0

    def _fill(self):
        while len(self.pending) < self.lookahead:
            self.pending.append(self.pool.apply_async(
                _request, ((self.args + (self.next_k,)),)))
            self.next_k += 1

    def prefetch(self):
        """Waits for the requests made ahead; from now on the generators
        stay idle while any of them is left."""
        self._fill()
        for res in self.pending:
            res.wait()
        self.lookahead = 1

    def take(self):
        self._fill()
        res = self.pending.popleft()
        if not res.ready():
            t0 = time.monotonic()
            res.wait()
            self.wait_s += time.monotonic() - t0
            self.late += 1
        out = res.get()
        self.taken += 1
        self._fill()
        return out

    def close(self):
        self.pool.terminate()
        self.pool.join()


def _relay(ctl, s):
    cfg, mix, seed = s['cfg'], s['mix'], s['seed']
    dt = spec.doctype(cfg)
    conn = wire.Conn(s['sock'])
    ahead = _Ahead(cfg, mix, seed)
    sent = {}           # k -> (n_ops, counts, t_send)
    acks = {}           # k -> (t_ack, failed docs)
    served = {}         # (doc index, round) -> patch
    errors = []
    sampled = {}        # slot -> sampled doc indexes

    def sample(slot):
        if slot not in sampled:
            sampled[slot] = _sampled(cfg, mix, seed, slot)
        return sampled[slot]

    def receive(deadline=None):
        if deadline is not None:
            conn.sock.settimeout(max(0.001, deadline - time.monotonic()))
        try:
            body = conn.read_body()
        finally:
            conn.sock.settimeout(None)
        t = time.monotonic()
        resp = wire.unpack(body)
        k = resp.get('id', 0) - 1
        if 'error' in resp:
            errors.append(resp['error'])
            acks[k] = (t, -1)
            return
        bad = 0
        for slot, r in _parts(mix, k):
            keep = set(sample(slot))
            for i in _slot_docs(cfg, mix, slot):
                res = resp['result'].get(dt.doc_id(i))
                if not isinstance(res, dict) or 'error' in res:
                    bad += 1
                    if len(errors) < 5:
                        errors.append(res)
                if i in keep:
                    served[(i, r)] = res
        acks[k] = (t, bad)

    def pump(until_k=None, until_t=None):
        outstanding = sum(1 for k in sent if k not in acks)
        while True:
            while outstanding < mix['depth'] and (
                    (until_k is not None and ahead.taken < until_k) or
                    (until_t is not None and time.monotonic() < until_t)):
                k, body, counts = ahead.take()
                t = time.monotonic()
                conn.send(body)
                sent[k] = (sum(counts.values()), counts, t)
                outstanding += 1
            if not outstanding:
                return
            receive()
            outstanding -= 1

    def setup(_):
        t0 = time.monotonic()
        pump(until_k=mix['warm_requests'])
        loaded = time.monotonic() - t0
        ahead.prefetch()
        ahead.wait_s = 0.0
        ahead.late = 0
        return {'load_s': loaded, 'errors': errors[:3]}

    def window(span):
        _t_open, t_close = span
        pump(until_t=t_close)
        deadline = t_close + DRAIN_S
        try:
            while any(k not in acks for k in sent):
                receive(deadline)
        except (OSError, ValueError):
            pass
        t_end = time.monotonic()
        first = mix['warm_requests']
        return {'sent': {k: v for k, v in sent.items() if k >= first},
                'acks': {k: v for k, v in acks.items() if k >= first},
                't_end': t_end,
                'gen_wait_s': ahead.wait_s, 'gen_late': ahead.late,
                'errors': errors[:3]}

    def readback(_):
        """The sampled docs of every slot a window request touched: the
        rounds sent and answered, each patch served, the state now."""
        first = mix['warm_requests']
        slots = sorted({slot for k in sent if k >= first
                        for slot, _r in _parts(mix, k)})
        out = []
        for slot in slots:
            rounds = [r for r in range(1, mix['rounds_per_doc'] + 1)
                      if slot + r - 1 in sent]
            acked = [r for r in rounds if slot + r - 1 in acks]
            for i in sample(slot):
                state = conn.call({'cmd': 'get_patch', 'id': 0,
                                   'doc': dt.doc_id(i)})
                out.append({'doc': i, 'rounds': rounds, 'acked': acked,
                            'served': {r: served.get((i, r))
                                       for r in rounds},
                            'state': state.get('result', state)})
        return out

    try:
        children.serve(ctl, {'setup': setup, 'window': window,
                             'readback': readback})
    finally:
        ahead.close()
        conn.close()


def _compare(job):
    """One sampled doc against the reference: its rounds in order, each
    served patch, then the state read back."""
    from reference import backend
    cfg, seed, d = job['cfg'], job['seed'], job['doc']
    dt = spec.doctype(cfg)
    out = {'wrong_patches': 0, 'unanswered': 0, 'wrong_states': 0,
           'patches_compared': 0}
    state = backend.init()
    for r in d['rounds']:
        state, patch = backend.apply_changes(
            state, dt.round_changes(cfg, seed, d['doc'], r))
        if r not in d['acked']:
            out['unanswered'] += 1
            continue
        out['patches_compared'] += 1
        if d['served'].get(r) != patch:
            out['wrong_patches'] += 1
    if d['state'] != backend.get_patch(state):
        out['wrong_states'] += 1
    return out


class Run(object):
    def __init__(self, cell, seed, sock):
        self.cfg, self.mix, self.seed = cell['config'], cell['traffic'], seed
        self.child = children.Child(
            _relay, {'sock': sock, 'cfg': self.cfg, 'mix': self.mix,
                     'seed': seed}, 'catchup-relay')

    def setup(self, seconds):
        self.seconds = seconds
        return self.child.ask('setup', timeout=600)

    def open(self):
        """Opens the window now; returns its bounds."""
        t_open = time.monotonic()
        self._span = (t_open, t_open + self.seconds)
        self.child.tell('window', self._span)
        return self._span

    def wait(self):
        """Waits until the window has closed and every request it sent is
        answered (a minute past the close at most); returns the clock read
        after that wait."""
        self._res = self.child.wait('window', timeout=None)
        return self._res['t_end']

    def finish(self):
        """Reads the sampled docs back; returns the client-side numbers."""
        res = self._res
        self.docs = self.child.ask('readback', timeout=600)
        acked = [k for k, (t, bad) in res['acks'].items() if bad == 0]
        whole = sum(res['sent'][k][0] for k in acked)
        counts = {}
        for k in acked:
            for a, v in res['sent'][k][1].items():
                counts[a] = counts.get(a, 0) + v
        failed = sum(1 for k in res['sent']
                     if k not in res['acks'] or res['acks'][k][1] != 0)
        # seconds between successive answers, from the opening: a stall
        # shows as one long interval
        times = sorted([self._span[0]] +
                       [t for t, _b in res['acks'].values()])
        return {'attempted': len(res['sent']), 'failed': failed,
                'ops_done': whole, 'requests_acked': len(acked),
                'answer_intervals_s': [round(b - a, 3)
                                       for a, b in zip(times, times[1:])],
                'window_s': res['t_end'] - self._span[0],
                'op_counts': counts,
                'gen_wait_s': res['gen_wait_s'], 'gen_late': res['gen_late'],
                'errors': res['errors']}

    def check(self):
        jobs = [{'cfg': self.cfg, 'seed': self.seed, 'doc': d}
                for d in self.docs]
        got = check.run(_compare, jobs)
        print('[check] %d patches of %d sampled docs compared'
              % (got.get('patches_compared', 0), len(jobs)),
              file=sys.stderr, flush=True)
        return [('wrong_patches', got.get('wrong_patches', 0), 0),
                ('wrong_states', got.get('wrong_states', 0), 0),
                ('unanswered', got.get('unanswered', 0), 0)]

    def close(self):
        self.child.stop()
