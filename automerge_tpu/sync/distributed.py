"""Multi-process replica sync: the Connection protocol across process
boundaries (the DCN stand-in for multi-host deployment).

The reference's transport abstraction is a callback-based message channel
carrying ``{docId, clock, changes}`` objects
(`/root/reference/src/connection.js:18-22,51-56`).  The TPU rebuild keeps
that schema verbatim and maps the two halves of the protocol onto the two
kinds of interconnect a TPU pod has:

* **Clock gossip (numeric, dense)** rides jax collectives: every process
  contributes its replicas' ``[R_local, A]`` clock matrix and a
  ``process_allgather`` (DCN all-gather; the Gloo backend on CPU hosts)
  assembles the global ``[R, A]`` matrix.  Planning then runs the SAME
  device kernel (`parallel.replica.batched_plan`) in every process --
  deterministic inputs, deterministic plan, zero further coordination.
* **Change shipping (bytes, sparse)** crosses a TCP mesh between
  processes: each planned shipment whose sender is local pulls raw change
  bytes from the sender pool and sends one ``{docId, clock, changes}``
  msgpack message (4-byte length prefix framing, like the sidecar's
  msgpack mode) to the process hosting the receiver.

Faults heal exactly like the single-process `BatchedReplicaSet`:
duplicate deliveries are seq-dedup no-ops (reference op_set.js:255-260)
and causal gaps buffer in the receiver's queue until a later round.

Dryrun: ``python -m automerge_tpu.sync.distributed --processes 2``
spawns the worker processes, seeds disjoint per-replica streams, runs
catch-up, and verifies cross-process convergence + oracle equality
(tests/test_distributed_sync.py drives the same entry).
"""

import json
import os
import socket
import struct
import sys
import time

import numpy as np

from ..utils.common import env_float

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# collective helpers (DCN stand-in: Gloo on CPU hosts, real DCN on pods)
# ---------------------------------------------------------------------------

def allgather_blob(data):
    """All-gather one variable-length bytes blob per process; returns the
    list of every process's blob, in process order.  Length-pads through
    two fixed-shape array all-gathers (collectives need static shapes)."""
    from jax.experimental import multihost_utils as mh
    lens = mh.process_allgather(np.array([len(data)], np.int32))
    lens = np.asarray(lens).reshape(-1)
    width = max(int(lens.max()), 1)
    buf = np.zeros((width,), np.uint8)
    if data:
        buf[:len(data)] = np.frombuffer(data, np.uint8)
    got = np.asarray(mh.process_allgather(buf))
    return [got[p, :int(lens[p])].tobytes() for p in range(got.shape[0])]


def allgather_clock_mats(local_mat):
    """All-gather the per-process ``[R_local, A]`` clock matrix into the
    global ``[R, A]`` matrix (replicas concatenated in process order) --
    the clock-union half of the reference's advertisement rounds as ONE
    collective."""
    from jax.experimental import multihost_utils as mh
    got = np.asarray(mh.process_allgather(local_mat))
    return got.reshape(-1, local_mat.shape[1])


# ---------------------------------------------------------------------------
# TCP mesh (change shipping)
# ---------------------------------------------------------------------------

class ProcessMesh:
    """Tiny synchronous P-process TCP mesh.  Each process listens on
    ``port_base + pid``; sender connections open lazily and persist.
    Messages are msgpack bytes behind a 4-byte big-endian length prefix
    (the sidecar's msgpack framing)."""

    def __init__(self, pid, n_processes, port_base):
        self.pid = pid
        self.n = n_processes
        self.port_base = port_base
        self.server = socket.create_server(('127.0.0.1', port_base + pid),
                                           backlog=n_processes)
        self.out = {}
        self.inbox = {}   # peer pid -> connected socket (accepted)

    def _connect(self, peer):
        sock = self.out.get(peer)
        if sock is None:
            # capped exponential backoff under one overall deadline: a
            # slow-starting peer (cold jax init, supervised restart)
            # must not abort the whole mesh, while a genuinely absent
            # one still fails within the deadline (default 60s -- a
            # loaded CI host cold-starting P jax processes can eat most
            # of 30; AMTPU_MESH_CONNECT_DEADLINE_S overrides).  Early
            # attempts stay cheap (short connect timeout, short sleep);
            # later ones back off so P processes don't hammer a
            # struggling listener.
            deadline = time.time() + env_float(
                'AMTPU_MESH_CONNECT_DEADLINE_S', 60)
            delay, timeout = 0.05, 1.0
            while True:
                try:
                    sock = socket.create_connection(
                        ('127.0.0.1', self.port_base + peer),
                        timeout=min(timeout, max(0.1,
                                                 deadline - time.time())))
                    break
                except OSError:
                    if time.time() > deadline:
                        raise
                    time.sleep(min(delay, max(0.0,
                                              deadline - time.time())))
                    delay = min(delay * 1.6, 2.0)
                    timeout = min(timeout * 2, 5.0)
            sock.sendall(struct.pack('>I', self.pid))
            self.out[peer] = sock
        return sock

    def _accept_from(self, peer):
        # bounded accept: a peer that crashed before connecting must
        # surface as an error here, not wedge every surviving process
        self.server.settimeout(60)
        while peer not in self.inbox:
            try:
                conn, _ = self.server.accept()
            except socket.timeout:
                raise ConnectionError(
                    'peer %d never connected (crashed?)' % peer)
            hdr = self._read_exact(conn, 4)
            self.inbox[struct.unpack('>I', hdr)[0]] = conn
        return self.inbox[peer]

    @staticmethod
    def _read_exact(sock, n):
        parts = []
        while n:
            chunk = sock.recv(n)
            if not chunk:
                raise ConnectionError('peer closed')
            parts.append(chunk)
            n -= len(chunk)
        return b''.join(parts)

    def send(self, peer, payload):
        sock = self._connect(peer)
        sock.sendall(struct.pack('>I', len(payload)) + payload)

    def recv(self, peer):
        sock = self._accept_from(peer)
        n = struct.unpack('>I', self._read_exact(sock, 4))[0]
        return self._read_exact(sock, n)

    def close(self):
        for sock in self.out.values():
            sock.close()
        for sock in self.inbox.values():
            sock.close()
        self.server.close()


# ---------------------------------------------------------------------------
# the distributed replica set
# ---------------------------------------------------------------------------

class DistributedReplicaSet:
    """``n_local`` pool-backed replicas in THIS process, synchronized with
    the other processes' replicas.  Global replica r lives in process
    ``r // n_local`` (all processes host the same count)."""

    def __init__(self, pid, n_processes, n_local, port_base,
                 pool_factory=None):
        if pool_factory is None:
            from ..native import NativeDocPool
            pool_factory = NativeDocPool
        self.pid = pid
        self.n_processes = n_processes
        self.n_local = n_local
        self.replicas = [pool_factory() for _ in range(n_local)]
        self.mesh = ProcessMesh(pid, n_processes, port_base)
        self.doc_ids = []
        self._doc_set = set()

    # -- local ingestion ------------------------------------------------

    def apply_batch(self, local_replica, changes_by_doc):
        for doc_id in changes_by_doc:
            if doc_id not in self._doc_set:
                self._doc_set.add(doc_id)
                self.doc_ids.append(doc_id)
        return self.replicas[local_replica].apply_batch(changes_by_doc)

    # -- one gossip round ----------------------------------------------

    def _exchange_metadata(self):
        """Doc ids + per-doc actor tables must agree globally before the
        numeric collective; a small msgpack blob all-gather carries them."""
        local = {
            'docs': sorted(self._doc_set),
            'actors': {d: sorted(
                {a for r in self.replicas
                 for a in r.get_clock(d)['clock']})
                for d in self._doc_set},
        }
        blobs = allgather_blob(json.dumps(local).encode())
        docs = sorted({d for b in blobs for d in json.loads(b)['docs']})
        actors = {}
        for b in blobs:
            for d, acts in json.loads(b)['actors'].items():
                actors.setdefault(d, set()).update(acts)
        return docs, {d: sorted(a) for d, a in actors.items()}

    def _one_round(self):
        import msgpack

        from ..parallel.replica import batched_plan
        from ..utils.common import doc_key as _doc_key
        from ..utils.wire import array_header, map_header, \
            read_array_header

        docs, actors_by_doc = self._exchange_metadata()
        if not docs:
            return 0
        A = 1
        while A < max(max((len(a) for a in actors_by_doc.values()),
                          default=1), 1):
            A *= 2
        D = 1
        while D < len(docs):
            D *= 2

        # local [D, R_local, A] clocks -> global [D, R, A] via ONE
        # collective (flattened to keep the gather a single fixed shape)
        local = np.zeros((D, self.n_local, A), np.int32)
        for i, d in enumerate(docs):
            idx = {a: j for j, a in enumerate(actors_by_doc[d])}
            for rl, pool in enumerate(self.replicas):
                for a, s in pool.get_clock(d)['clock'].items():
                    local[i, rl, idx[a]] = s
        gathered = allgather_clock_mats(
            local.transpose(1, 0, 2).reshape(self.n_local, D * A))
        R = gathered.shape[0]
        mats = gathered.reshape(R, D, A).transpose(1, 0, 2)
        mats = np.ascontiguousarray(mats)

        # identical deterministic plan in every process
        frontier, deficit, at_frontier = (np.asarray(x)
                                          for x in batched_plan(mats))
        planned_total = 0
        # outbox[peer pid] -> list of {docId, clock, changes-splice}
        outbox = {p: [] for p in range(self.n_processes)}

        for i, doc_id in enumerate(docs):
            if not deficit[i].any():
                continue
            acts = actors_by_doc[doc_id]
            holder = np.argmax(at_frontier[i], axis=0)
            recvs, streams = np.nonzero(deficit[i] > 0)
            ships = {}   # (sender, receiver) -> [(actor, after_seq)]
            for r, a in zip(recvs.tolist(), streams.tolist()):
                if a >= len(acts):
                    continue
                s = int(holder[a])
                ships.setdefault((s, r), []).append(
                    (acts[a], int(mats[i, r, a])))
            for (s, r), streams_list in ships.items():
                planned_total += len(streams_list)
                sp, rp = s // self.n_local, r // self.n_local
                if sp != self.pid:
                    continue
                # sender is local: build one Connection-schema message
                sender_pool = self.replicas[s % self.n_local]
                arrays = []
                total = 0
                for actor, after_seq in streams_list:
                    buf = sender_pool.get_changes_for_actor_bytes(
                        doc_id, actor, after_seq)
                    cnt, off = read_array_header(buf)
                    if cnt:
                        arrays.append(memoryview(buf)[off:])
                        total += cnt
                if not total:
                    continue
                clock = sender_pool.get_clock(doc_id)['clock']
                # {docId, clock, changes} -- reference schema verbatim
                # (src/connection.js:51-56); changes spliced raw
                msg = [msgpack.packb({'to': r, 'docId': _doc_key(doc_id)},
                                     use_bin_type=True),
                       msgpack.packb(clock, use_bin_type=True),
                       array_header(total)] + arrays
                outbox[rp].append(b''.join(msg))

        # synchronous round: every peer sends exactly ONE batch message
        # (possibly empty) to every other peer, so the receive loop is a
        # fixed exchange (mirrors the scripted delivery of the
        # reference's connection tests).  Sends run on threads so big
        # payloads can't deadlock the round: if every process blocked in
        # sendall() before reaching its recv loop, catch-up batches
        # larger than the kernel socket buffers would wedge all peers.
        import threading
        errors = []

        def ship(peer):
            try:
                batch = msgpack.packb(len(outbox[peer]), use_bin_type=True)
                self.mesh.send(peer, batch + b''.join(
                    msgpack.packb(m, use_bin_type=True)
                    for m in outbox[peer]))
            except Exception as e:        # surfaced after join
                errors.append((peer, e))

        senders = [threading.Thread(target=ship, args=(peer,))
                   for peer in range(self.n_processes) if peer != self.pid]
        for t in senders:
            t.start()

        inbound = list(outbox[self.pid])
        for peer in range(self.n_processes):
            if peer == self.pid:
                continue
            data = self.mesh.recv(peer)
            unp = msgpack.Unpacker(raw=False)
            unp.feed(data)
            count = unp.unpack()
            for _ in range(count):
                inbound.append(unp.unpack())
        for t in senders:
            t.join()
        if errors:
            raise ConnectionError('send to peer %d failed: %s' % errors[0])

        # deliver: group by local receiver, one apply_batch_bytes each
        per_receiver = {}
        for m in inbound:
            unp = msgpack.Unpacker(raw=True)
            unp.feed(m)
            head = unp.unpack()
            r = head[b'to'] if isinstance(head, dict) else head['to']
            doc_key = head[b'docId'] if isinstance(head, dict) \
                else head['docId']
            body = m[unp.tell():]
            per_receiver.setdefault(int(r), {}).setdefault(
                doc_key if isinstance(doc_key, str)
                else doc_key.decode(), []).append(body)

        for r, by_doc in per_receiver.items():
            pool = self.replicas[r % self.n_local]
            parts = [map_header(len(by_doc))]
            for doc_id, messages in by_doc.items():
                parts.append(msgpack.packb(_doc_key(doc_id),
                                           use_bin_type=True))
                # splice: each message body is clock + array of changes;
                # re-frame as ONE array of all changes.  The advertised
                # sender clock feeds receiver-side dedup, the same role
                # the reference Connection's clock maps play
                # (src/connection.js:75-90): when the receiver's clock
                # already dominates the advertisement, every change in
                # the message is known and the splice skips the body.
                try:
                    own = pool.get_clock(doc_id)['clock']
                except Exception:
                    own = {}             # receiver has no state yet
                bodies = []
                total = 0
                for body in messages:
                    unp = msgpack.Unpacker(raw=False)
                    unp.feed(body)
                    advertised = unp.unpack()    # sender clock
                    off = unp.tell()
                    if advertised and own and all(
                            own.get(a, 0) >= s
                            for a, s in advertised.items()):
                        continue
                    cnt, hoff = read_array_header(body[off:])
                    total += cnt
                    bodies.append(body[off + hoff:])
                parts.append(array_header(total))
                parts.extend(bodies)
            pool.apply_batch_bytes(b''.join(parts))
        return planned_total

    def catch_up(self, max_rounds=None):
        if max_rounds is None:
            max_rounds = 4 * self.n_processes * self.n_local + 8
        rounds = []
        for _ in range(max_rounds):
            planned = self._one_round()
            rounds.append(planned)
            if planned == 0:
                return rounds
        raise RuntimeError('distributed catch-up did not converge in %d '
                           'rounds' % max_rounds)

    # -- verification ---------------------------------------------------

    def global_trees(self):
        """All-gathers every replica's materialized tree per doc; every
        process returns the same [R][doc] structure."""
        from .replica_set import patch_to_tree
        local = {
            str(d): [repr(patch_to_tree(r.get_patch(d)))
                     for r in self.replicas]
            for d in self.doc_ids}
        blobs = allgather_blob(json.dumps(local).encode())
        return [json.loads(b) for b in blobs]

    def close(self):
        self.mesh.close()


# ---------------------------------------------------------------------------
# dryrun worker + launcher
# ---------------------------------------------------------------------------

def _worker(pid, n_processes, coord_port, mesh_port_base):
    os.environ['JAX_PLATFORMS'] = 'cpu'
    from ..utils.jaxenv import enable_cpu_collectives
    import jax
    jax.config.update('jax_platforms', 'cpu')
    enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address='127.0.0.1:%d' % coord_port,
        num_processes=n_processes, process_id=pid)

    from .. import backend as Oracle
    from ..utils.common import ROOT_ID

    n_local = 2
    rs = DistributedReplicaSet(pid, n_processes, n_local,
                               mesh_port_base)
    # disjoint streams: global replica r authors actor 'a<r>' on 2 docs
    union = {d: [] for d in range(2)}
    for d in range(2):
        for g in range(n_processes * n_local):
            actor = 'a%02d' % g
            chs = [{'actor': actor, 'seq': s, 'deps': {},
                    'ops': [{'action': 'set', 'obj': ROOT_ID,
                             'key': 'k%d' % ((s + g) % 5),
                             'value': '%s-%d' % (actor, s)}]}
                   for s in range(1, 4)]
            union[d].extend(chs)
            if g // n_local == pid:
                rs.apply_batch(g % n_local, {'doc-%d' % d: chs})

    rounds = rs.catch_up()

    # verification: every replica in every process converged to the
    # oracle union
    from .replica_set import patch_to_tree
    want = {}
    for d in range(2):
        st = Oracle.init()
        st, _ = Oracle.apply_changes(st, union[d])
        want['doc-%d' % d] = repr(patch_to_tree(Oracle.get_patch(st)))
    trees = rs.global_trees()
    for proc_trees in trees:
        for d in range(2):
            for tree in proc_trees['doc-%d' % d]:
                assert tree == want['doc-%d' % d], \
                    'divergence at pid %d doc %d' % (pid, d)
    rs.close()
    print('DISTRIBUTED-OK pid=%d rounds=%s' % (pid, rounds), flush=True)


#: output signatures of the Gloo/coordination-service infrastructure
#: flake cascade: the size-mismatch race aborts one worker at random
#: ("op.preamble.length <= op.nbytes"), and every OTHER worker then dies
#: of heartbeat timeout / shutdown-barrier failure -- so the victim a
#: caller inspects first rarely shows the preamble text itself.  The
#: widened set (ISSUE 8 deflake) adds the transport-teardown shapes the
#: same cascade surfaces on this host (peer reset / broken pipe when
#: the aborted worker's sockets die first, and the TCP-store bind race
#: when a retry reuses a port the kernel still holds in TIME_WAIT).
#: Deliberately NOT bare gRPC status tokens (UNAVAILABLE etc.): those
#: appear in too many REAL failure texts, and burning retries on a
#: deterministic regression both slows the lane 4x and reports the
#: wrong attempt's error.
_FLAKY_SIGNATURES = ('op.preamble.length', 'heartbeat timeout',
                     'Shutdown barrier', 'coordination service',
                     'Connection reset by peer', 'Broken pipe',
                     'Address already in use')


def launch(n_processes=2, timeout=300, _retries=3):
    """Spawns the dryrun workers; returns their outputs.  Raises on any
    non-zero exit.  Bounded retries absorb the Gloo TCP transport's
    known size-mismatch race, which aborts a worker process at random
    under back-to-back collectives of varying shapes (and takes the
    rest of the mesh down with coordination-service cascade errors) --
    an infrastructure flake, not a convergence bug.  ALL outputs are
    collected before deciding: the flake signature may sit in a later
    worker's output than the first non-zero exit."""
    import subprocess
    with socket.socket() as probe:
        probe.bind(('127.0.0.1', 0))
        coord_port = probe.getsockname()[1]
    mesh_port_base = coord_port + 1000 if coord_port < 64000 else 21000
    procs = [
        subprocess.Popen(
            [sys.executable, '-m', 'automerge_tpu.sync.distributed',
             '--worker', str(pid), '--processes', str(n_processes),
             '--coord-port', str(coord_port),
             '--mesh-port-base', str(mesh_port_base)],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
            env=dict(os.environ, JAX_PLATFORMS='cpu'))
        for pid in range(n_processes)]
    outs = []
    failed = None
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            for q in procs:
                try:
                    o, _ = q.communicate(timeout=10)
                except Exception:
                    o = ''
                outs.append(o or '')
            # a wedged mesh (one worker died pre-abort) hangs the rest
            # at a collective until the deadline.  Retry ONLY that
            # shape: a worker that exited by itself (not our SIGKILL)
            # or a flake signature in any partial output -- a mesh
            # where EVERY worker hangs is a real deadlock and must
            # surface, not burn retries
            died_alone = any(q.returncode not in (0, -9) for q in procs)
            flaky = any(sig in o for o in outs
                        for sig in _FLAKY_SIGNATURES)
            if _retries > 0 and (died_alone or flaky):
                return launch(n_processes, timeout, _retries - 1)
            raise
        outs.append(out)
        if p.returncode != 0 and failed is None:
            failed = (p.returncode, out)
    if failed is not None:
        rc, out = failed
        if _retries > 0 and any(sig in o for o in outs
                                for sig in _FLAKY_SIGNATURES):
            return launch(n_processes, timeout, _retries - 1)
        raise RuntimeError('worker failed (rc=%d):\n%s' % (rc, out))
    return outs


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--processes', type=int, default=2)
    ap.add_argument('--worker', type=int, default=None)
    ap.add_argument('--coord-port', type=int, default=None)
    ap.add_argument('--mesh-port-base', type=int, default=None)
    args = ap.parse_args(argv)
    if args.worker is not None:
        _worker(args.worker, args.processes, args.coord_port,
                args.mesh_port_base)
        return 0
    for out in launch(args.processes):
        sys.stdout.write(out)
    return 0


if __name__ == '__main__':
    sys.exit(main())
