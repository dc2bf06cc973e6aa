"""automerge_tpu.storage -- cold-state economics (ISSUE 10,
docs/STORAGE.md).

Three pieces, wired through the pool, the sidecar WAL, and the serve
gateway:

  * :mod:`.columnar` -- the delta/RLE columnar change codec
    (`encode_columnar` / `decode_columnar`, byte-round-trip
    guaranteed);
  * checkpoint containers (this module) -- `pack_checkpoint` /
    `unpack_checkpoint`: the v2 ``amtpu-doc-v2c`` container a
    `pool.save()` emits (columnar snapshot chunks behind the settled
    frontier + a columnar tail), plus v1 compatibility for every
    pre-existing blob;
  * :mod:`.coldstore` -- the disk tier + LRU evictor the gateway uses
    for working-set >> RAM (``AMTPU_RESIDENT_DOCS_MAX``).

``AMTPU_STORAGE_FORMAT=json`` is the escape hatch / parity oracle:
save() then emits the PR-4 v1 container (raw change history) and
settled-history GC is a no-op -- the A/B arm the storage gate compares
against, same pattern as ``AMTPU_FANOUT_VECTOR``.
"""

import msgpack

from .. import telemetry
from ..utils.common import env_str
from .columnar import (corrupt_raises_value_error,  # noqa: F401
                       decode_columnar, decode_columnar_meta,
                       encode_columnar, storage_native_on)

FORMAT_V1 = 'amtpu-doc-v1'
FORMAT_V2 = 'amtpu-doc-v2c'

#: fixed byte prefixes: both containers are msgpack maps opening with
#: their format key, so a prefix compare classifies a blob without a
#: parse (native._load_batch splices checkpoints at the byte level)
CKPT_V1_PREFIX = (b'\x82' + msgpack.packb('format') +
                  msgpack.packb(FORMAT_V1) + msgpack.packb('changes'))
CKPT_V2_PREFIX = (b'\x84' + msgpack.packb('format') +
                  msgpack.packb(FORMAT_V2))


def storage_format():
    """'columnar' (default) or 'json' (the v1 parity-oracle arm)."""
    fmt = env_str('AMTPU_STORAGE_FORMAT', 'columnar')
    if fmt not in ('columnar', 'json'):
        raise ValueError('AMTPU_STORAGE_FORMAT must be columnar|json, '
                         'got %r' % (fmt,))
    return fmt


def split_changes_array(buf):
    """Splits a raw msgpack array of changes into per-change byte
    slices without building any Python objects (Unpacker.skip walks
    the framing)."""
    buf = bytes(buf)
    u = msgpack.Unpacker(None, max_buffer_size=0)
    u.feed(buf)
    n = u.read_array_header()
    out = []
    start = u.tell()
    for _ in range(n):
        u.skip()
        end = u.tell()
        out.append(buf[start:end])
        start = end
    return out


def join_changes_array(raws):
    """Inverse of `split_changes_array`: one msgpack array of the raw
    change byte strings."""
    out = bytearray()
    n = len(raws)
    if n < 16:
        out.append(0x90 | n)
    elif n < (1 << 16):
        out += b'\xdc' + n.to_bytes(2, 'big')
    else:
        out += b'\xdd' + n.to_bytes(4, 'big')
    for raw in raws:
        out += raw
    return bytes(out)


def pack_checkpoint_v1(raws):
    """The PR-4 container: raw change history, application order."""
    return CKPT_V1_PREFIX + join_changes_array(raws)


def pack_checkpoint(frontier, chunks, tail_raws):
    """The v2 columnar container: settled snapshot chunks (columnar
    blobs, application order, exactly the changes <= `frontier`) + the
    tail (everything after, columnar-encoded here).  Loading replays
    chunks then tail and re-establishes the frontier."""
    telemetry.metric('storage.save_v2')
    return (CKPT_V2_PREFIX +
            msgpack.packb('frontier') +
            msgpack.packb(dict(frontier or {}), use_bin_type=True) +
            msgpack.packb('chunks') +
            msgpack.packb(list(chunks), use_bin_type=True) +
            msgpack.packb('tail') +
            msgpack.packb(encode_columnar(tail_raws),
                          use_bin_type=True))


def is_checkpoint(data):
    return data.startswith(CKPT_V1_PREFIX) \
        or data.startswith(CKPT_V2_PREFIX)


def unpack_checkpoint(data):
    """-> (frontier, chunks, tail_raws): per-format normalize.  v1
    blobs have no frontier and no chunks; v2 blobs decode their tail
    here (chunks stay encoded -- the caller adopts them verbatim into
    the doc's storage state).  A corrupted container surfaces as
    ValueError whatever the decoder tripped on internally (zlib,
    struct, an out-of-range table index) -- callers map it to their
    RangeError contract."""
    if data.startswith(CKPT_V1_PREFIX):
        with corrupt_raises_value_error('checkpoint container'):
            return {}, [], split_changes_array(
                memoryview(data)[len(CKPT_V1_PREFIX):])
    if not data.startswith(CKPT_V2_PREFIX):
        raise ValueError('not an amtpu checkpoint container')
    with corrupt_raises_value_error('checkpoint container'):
        obj = msgpack.unpackb(data, raw=False, strict_map_key=False)
        return (obj.get('frontier') or {},
                list(obj.get('chunks') or ()),
                decode_columnar(obj['tail']))


def unpack_checkpoint_parts(data):
    """v2-only LAZY parse: (frontier, chunks, tail_blob) without
    decoding anything columnar -- the native arena-direct loader
    (`amtpu_begin_columnar`) takes the blobs as-is, so a cold restart
    never builds Python change objects.  Corruption raises ValueError
    like `unpack_checkpoint`."""
    if not data.startswith(CKPT_V2_PREFIX):
        raise ValueError('not an amtpu v2 checkpoint container')
    with corrupt_raises_value_error('checkpoint container'):
        obj = msgpack.unpackb(data, raw=False, strict_map_key=False)
        tail = obj.get('tail')
        if not isinstance(tail, (bytes, bytearray)):
            raise ValueError('checkpoint tail missing')
        chunks = list(obj.get('chunks') or ())
        if not all(isinstance(c, (bytes, bytearray)) for c in chunks):
            raise ValueError('checkpoint chunks not bytes')
        return (obj.get('frontier') or {}, [bytes(c) for c in chunks],
                bytes(tail))


def checkpoint_raw_changes(data):
    """Every raw change of a checkpoint (either format), application
    order -- what load() replays.  Corruption surfaces as ValueError
    (see `unpack_checkpoint`)."""
    _frontier, chunks, tail = unpack_checkpoint(data)
    out = []
    for chunk in chunks:
        out.extend(decode_columnar(chunk))
    out.extend(tail)
    return out
