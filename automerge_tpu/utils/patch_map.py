"""A batched apply's result kept as the pool's own msgpack bytes.

The pool answers a batch with one msgpack map ``{doc_key: patch}``.  A
caller that only forwards those patches (the gateway's flush) need not
decode them into dicts and encode them again: inside
:func:`byte_results`, ``native._apply_batch_dicts`` returns a
:class:`PatchMap` over the bytes, which finds each doc's span in one
walk and decodes a doc only when something reads it.  The gateway then
answers each request with a :class:`SubMap` or :class:`DocResult` view
and :func:`pack_body` splices the spans into the response frame.  Every
other caller keeps getting decoded dicts.
"""

import contextlib
import contextvars
from collections.abc import Mapping

import msgpack

from ..resilience import is_quarantined
from .common import doc_key
from .wire import map_header

_BYTE_RESULTS = contextvars.ContextVar('amtpu_byte_results', default=False)

#: the packed head of a quarantine envelope (`resilience.error_envelope`:
#: a two-entry map whose first key is 'error'); a patch opens with five
#: entries, 'clock' first
_ENVELOPE_HEAD = b'\x82\xa5error'


@contextlib.contextmanager
def byte_results():
    """Within this block a batched apply returns a `PatchMap` instead of
    decoded dicts.  The gateway's flush wraps its one `pool.apply_batch`
    call in it, so whatever wraps that call sees the mapping too."""
    token = _BYTE_RESULTS.set(True)
    try:
        yield
    finally:
        _BYTE_RESULTS.reset(token)


def wanted():
    """True inside `byte_results`."""
    return _BYTE_RESULTS.get()


def _unpack(buf):
    return msgpack.unpackb(buf, raw=False, strict_map_key=False)


class PatchMap(Mapping):
    """Read-only ``{doc_id: result}`` over the pool's result bytes, in
    the request's doc order and keyed by the caller's doc ids (an int id
    reads the pool's ``'i:<n>'`` entry).

    A doc's result is decoded on its first read and cached: later reads,
    and any edit made to that dict, see the same object, and `packed`
    re-encodes it.  `quarantined` holds the docs whose result is a
    resilience error envelope, found without decoding a patch."""

    def __init__(self, raw, doc_ids):
        walk = msgpack.Unpacker(raw=False, strict_map_key=False,
                                max_buffer_size=max(1, len(raw)))
        walk.feed(raw)
        spans = {}
        for _ in range(walk.read_map_header()):
            key = walk.unpack()
            start = walk.tell()
            walk.skip()
            spans[key] = (start, walk.tell())
        self._raw = memoryview(raw)
        # a doc the pool did not answer raises KeyError here, as the
        # decoded path's lookup does
        self._spans = {d: spans[doc_key(d)] for d in doc_ids}
        self._decoded = {}
        self.quarantined = {
            d for d, (s, e) in self._spans.items()
            if self._raw[s:s + len(_ENVELOPE_HEAD)] == _ENVELOPE_HEAD
            and is_quarantined(_unpack(self._raw[s:e]))}

    def __getitem__(self, doc_id):
        try:
            return self._decoded[doc_id]
        except KeyError:
            s, e = self._spans[doc_id]
            got = self._decoded[doc_id] = _unpack(self._raw[s:e])
            return got

    def __contains__(self, doc_id):
        return doc_id in self._spans

    def __iter__(self):
        return iter(self._spans)

    def __len__(self):
        return len(self._spans)

    @property
    def n_decoded(self):
        """Docs whose result something has read."""
        return len(self._decoded)

    def packed(self, doc_id):
        """(msgpack of one doc's result, whether it is the pool's own
        bytes): a doc something decoded is encoded from that dict."""
        if doc_id in self._decoded:
            return msgpack.packb(self._decoded[doc_id],
                                 use_bin_type=True), False
        s, e = self._spans[doc_id]
        return self._raw[s:e], True


class SubMap(Mapping):
    """One request's ``{doc_id: result}`` over `docs`, in their order,
    read through a `PatchMap`."""

    def __init__(self, patches, docs):
        self.patches = patches
        self.docs = dict.fromkeys(docs)     # ordered, O(1) membership

    def __getitem__(self, doc_id):
        if doc_id not in self.docs:
            raise KeyError(doc_id)
        return self.patches[doc_id]

    def __iter__(self):
        return iter(self.docs)

    def __len__(self):
        return len(self.docs)

    def pack_into(self, parts):
        """Appends this map's msgpack to `parts`; returns how many docs
        went as the pool's bytes."""
        parts.append(map_header(len(self.docs)))
        n_raw = 0
        for d in self.docs:
            body, raw = self.patches.packed(d)
            parts.append(msgpack.packb(d, use_bin_type=True))
            parts.append(body)
            n_raw += raw
        return n_raw


class DocResult(Mapping):
    """One doc's result, read through a `PatchMap`."""

    def __init__(self, patches, doc_id):
        self.patches = patches
        self.doc_id = doc_id

    def __getitem__(self, key):
        return self.patches[self.doc_id][key]

    def __iter__(self):
        return iter(self.patches[self.doc_id])

    def __len__(self):
        return len(self.patches[self.doc_id])

    def pack_into(self, parts):
        body, raw = self.patches.packed(self.doc_id)
        parts.append(body)
        return int(raw)


def pack_body(resp):
    """The msgpack of a response dict whose values may be `SubMap` or
    `DocResult` views, as a list of bytes-like parts, and how many docs
    it carries as the pool's bytes."""
    if not any(isinstance(v, (SubMap, DocResult)) for v in resp.values()):
        return [msgpack.packb(resp, use_bin_type=True)], 0
    parts = [map_header(len(resp))]
    n_raw = 0
    for k, v in resp.items():
        parts.append(msgpack.packb(k, use_bin_type=True))
        if isinstance(v, (SubMap, DocResult)):
            n_raw += v.pack_into(parts)
        else:
            parts.append(msgpack.packb(v, use_bin_type=True))
    return parts, n_raw


def plain(obj):
    """`json.dumps` default hook: a view decodes to a plain dict."""
    if isinstance(obj, (SubMap, DocResult)):
        return dict(obj)
    raise TypeError('%s is not JSON serializable' % type(obj).__name__)
