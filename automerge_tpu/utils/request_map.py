"""An apply_batch request kept as its frame's msgpack bytes.

The pool takes a batch as one msgpack map ``{doc_key: [change, ...]}``.
A gateway that only forwards a client's changes need not decode them
into dicts and encode them again: :func:`read_request` walks an
``apply_batch`` frame once, decodes every top-level value but ``docs``,
and answers ``docs`` with a :class:`FrameDocs` that records where each
doc's changes array lies in the frame.  The flush merges requests into
a :class:`BatchDocs`, which the pool's ``apply_batch`` asks for its
payload (:meth:`BatchDocs.packed`): each doc's span spliced after its
packed key.  A doc's changes are decoded only when something reads
them.  Every other caller keeps passing dicts, and gets today's `packb`.

A span is spliced only where it already is what ``packb(unpackb(span))``
gives (the caller's `scan`, ``native.scan_changes``), so the pool
receives the bytes the decode-and-repack path would hand it; any other
doc is decoded on the reader.  A frame the walk cannot take whole is
decoded by `unpackb` as before, which also raises the same error for a
frame it refuses.
"""

from collections.abc import Mapping

import msgpack

from .. import telemetry
from .common import doc_key
from .wire import map_header, read_array_header


def _unpack(buf):
    return msgpack.unpackb(buf, raw=False, strict_map_key=False)


def _pack(obj):
    return msgpack.packb(obj, use_bin_type=True)


def _count_ops(changes):
    """Ops a changes list submits: the `OPS` counter's unit."""
    return sum(len(c.get('ops', ())) for c in changes)


def read_request(body, scan):
    """The request a msgpack frame holds.  An ``apply_batch`` frame
    whose ``docs`` is a non-empty map of arrays keeps them as a
    `FrameDocs` over `body`; every other frame decodes whole, as
    `unpackb` decodes it, and raises what `unpackb` raises.

    ``scan(body, spans)`` gives each span's op count, or -1 for a span
    that is not in canonical form (``native.scan_changes``)."""
    body = bytes(body)
    try:
        req = _walk(body)
    except Exception:
        # the walk is a fast path only: a frame it cannot take, unpackb
        # decodes or refuses exactly as it always did
        req = None
    if req is None:
        return _unpack(body)
    docs = req.get('docs')
    if not isinstance(docs, dict) or not docs:
        return req
    if req.get('cmd') != 'apply_batch':
        return _unpack(body)
    ops = dict(zip(docs, scan(body, list(docs.values()))))
    decoded = {}
    for d, n in ops.items():
        if n < 0:
            s, e = docs[d]
            try:
                decoded[d] = _unpack(body[s:e])
            except Exception:
                # the frame's own error, as unpackb raises it first
                return _unpack(body)
    if decoded:
        telemetry.metric('gateway.request_decoded_docs', len(decoded))
    req['docs'] = FrameDocs(
        body, docs, {d: n for d, n in ops.items() if n >= 0}, decoded)
    return req


def _is_map(b):
    return (b & 0xf0) == 0x80 or b in (0xde, 0xdf)


def _is_array(b):
    return (b & 0xf0) == 0x90 or b in (0xdc, 0xdd)


def _walk(body):
    """One Unpacker pass over a frame: the top-level map with every
    value decoded but a map-valued ``docs``, which becomes
    ``{doc_id: (start, end)}`` of each doc's changes.  None when
    ``docs`` holds a value that is not an array, or bytes follow the
    map; raises on a frame msgpack cannot walk."""
    walk = msgpack.Unpacker(raw=False, strict_map_key=False,
                            max_buffer_size=max(1, len(body)))
    walk.feed(body)
    req = {}
    for _ in range(walk.read_map_header()):
        key = walk.unpack()
        if key != 'docs' or not _is_map(body[walk.tell()]):
            req[key] = walk.unpack()
            continue
        spans = {}
        for _ in range(walk.read_map_header()):
            doc = walk.unpack()
            start = walk.tell()
            if not _is_array(body[start]):
                return None
            walk.skip()
            # a repeated doc keeps its place and takes the last span,
            # as unpackb's dict does
            spans[doc] = (start, walk.tell())
        req[key] = spans
    if walk.tell() != len(body):
        return None
    return req


class FrameDocs(Mapping):
    """Read-only ``{doc_id: [change, ...]}`` over an apply_batch frame's
    bytes, in frame order.

    `spans` gives each doc's ``(start, end)`` in `raw`; `ops` the op
    count of each doc whose span splices as it stands; `decoded` the
    docs already decoded.  A doc's changes are decoded on their first
    read and cached; each such decode counts in
    ``gateway.request_decoded_docs``."""

    def __init__(self, raw, spans, ops, decoded):
        self._raw = memoryview(raw)
        self._spans = spans
        self._ops = ops
        self._decoded = decoded

    def __getitem__(self, doc_id):
        try:
            return self._decoded[doc_id]
        except KeyError:
            s, e = self._spans[doc_id]
            got = self._decoded[doc_id] = _unpack(self._raw[s:e])
            telemetry.metric('gateway.request_decoded_docs')
            return got

    def __contains__(self, doc_id):
        return doc_id in self._spans

    def __iter__(self):
        return iter(self._spans)

    def __len__(self):
        return len(self._spans)

    def n_changes(self, doc_id):
        """Length of a doc's changes array, read from its header."""
        s, _ = self._spans[doc_id]
        return read_array_header(self._raw[s:s + 5])[0]

    def n_ops(self, doc_id):
        n = self._ops.get(doc_id)
        return _count_ops(self[doc_id]) if n is None else n

    def packed(self, doc_id):
        """(msgpack of one doc's changes, whether it is the frame's own
        bytes)."""
        if doc_id in self._ops:
            s, e = self._spans[doc_id]
            return self._raw[s:e], True
        return _pack(self[doc_id]), False


class BatchDocs(Mapping):
    """A flush's merged ``{doc_id: changes}``: each doc's changes are a
    list, or a doc of the `FrameDocs` they arrived in.  Merging follows
    `dict.update`: a doc merged again keeps its place and takes the
    later changes.  `n_spliced` counts the docs the last `packed()` took
    as their frame's own bytes."""

    def __init__(self):
        self._src = {}
        self.n_spliced = 0

    def add(self, doc_id, changes):
        self._src[doc_id] = changes

    def update(self, docs):
        if isinstance(docs, FrameDocs):
            for d in docs:
                self._src[d] = docs
        else:
            self._src.update(docs)

    def __getitem__(self, doc_id):
        src = self._src[doc_id]
        return src[doc_id] if isinstance(src, FrameDocs) else src

    def __contains__(self, doc_id):
        return doc_id in self._src

    def __iter__(self):
        return iter(self._src)

    def __len__(self):
        return len(self._src)

    def packed(self):
        """``(payload, n_ops)``: the pool's payload, one map header and
        then each doc's packed key and its frame's span (or its packed
        list), and the ops it submits."""
        # keyed as the dict path keys them: a doc whose key another doc
        # shares keeps the first one's place and the last one's changes
        keyed = {}
        n_ops = 0
        for d, src in self._src.items():
            keyed[doc_key(d)] = (d, src)
            n_ops += (src.n_ops(d) if isinstance(src, FrameDocs)
                      else _count_ops(src))
        parts = [map_header(len(keyed))]
        n_raw = 0
        for k, (d, src) in keyed.items():
            parts.append(_pack(k))
            if isinstance(src, FrameDocs):
                body, raw = src.packed(d)
                n_raw += raw
            else:
                body = _pack(src)
            parts.append(body)
        self.n_spliced = n_raw
        return b''.join(parts), n_ops
