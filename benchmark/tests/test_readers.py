"""The per-layer readers of the program's serving split, uploads and
transfer bytes, on a window written out by hand: each reads its span or
counter per op completed, and nothing where the program lacks it."""

import pytest

from harness import spec

OPS = 2_000_000

SPANS = {'decode_s_per_mop.catchup': 'gateway.decode',
         'repack_s_per_mop.catchup': 'pool.repack',
         'encode_s_per_mop.catchup': 'gateway.encode',
         'upload_s_per_mop.catchup': 'device.upload'}


def _ctx(spans=None, counters=None, ops=OPS):
    return {'client': {'ops_done': ops, 'window_s': 10.0},
            'program': {'spans': spans or {}, 'counters': counters or {},
                        'pool_batch': {'s': 0.0, 'n': 0}}}


@pytest.mark.parametrize('metric', sorted(SPANS))
def test_span_seconds_per_mop(metric):
    ctx = _ctx(spans={SPANS[metric]: {'s': 0.5, 'n': 17},
                      'scheduler.flush': {'s': 9.0, 'n': 17}})
    assert spec.reader(metric)(ctx) == pytest.approx(0.25)


@pytest.mark.parametrize('metric', sorted(SPANS))
def test_span_absent_reads_nothing(metric):
    # the parent program has none of these spans: its line leaves the
    # metric out
    ctx = _ctx(spans={'scheduler.flush': {'s': 9.0, 'n': 17}})
    assert spec.reader(metric)(ctx) is None
    assert spec.reader(metric)(_ctx(spans={SPANS[metric]: {
        's': 0.5, 'n': 1}}, ops=0)) is None


@pytest.mark.parametrize('metric, counter, other', [
    ('h2d_bytes_per_op.catchup', 'transfer.h2d_bytes', 'transfer.d2h_bytes'),
    ('d2h_bytes_per_op.catchup', 'transfer.d2h_bytes', 'transfer.h2d_bytes')])
def test_transfer_bytes_per_op(metric, counter, other):
    read = spec.reader(metric)
    assert read(_ctx(counters={counter: 56e6, other: 1e6})) \
        == pytest.approx(28.0)
    assert read(_ctx(counters={other: 1e6})) is None
    assert read(_ctx(counters={counter: 1e6}, ops=0)) is None
