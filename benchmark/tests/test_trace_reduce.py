"""The reduction from a device trace to busy time, module time and named
idle gaps, on small traces written out by hand and recorded on a chip."""

import glob
import gzip
import json
import os

import pytest

from harness import profile

DEV = '/device:TPU:0'
HOST = '/host:CPU'


def _ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


def test_busy_is_the_union_of_op_intervals():
    events = [
        _ev(DEV, 'XLA Modules', 'jit_resolve(17)', 0, 40e9),
        _ev(DEV, 'XLA Ops', 'fusion.1', 0, 10e9),
        _ev(DEV, 'XLA Ops', 'fusion.2', 5e9, 15e9),     # overlaps
        _ev(DEV, 'XLA Ops', 'fusion.3', 30e9, 10e9),
        _ev(DEV, 'XLA Modules', 'jit_emit(3)', 60e9, 20e9),
        _ev(DEV, 'XLA Ops', 'copy.1', 60e9, 20e9),
        _ev(HOST, 'python', 'PjitFunction(emit)', 40e9, 19e9),
        _ev(HOST, 'python', 'short', 41e9, 1e9),
        _ev(HOST, 'python', 'tail', 80e9, 20e9),
    ]
    got = profile.reduce(events)
    assert got['chips'] == 1
    assert got['busy_s'] == pytest.approx(50.0)
    assert got['span_s'] == pytest.approx(100.0)
    assert got['modules'] == {'jit_resolve': 40.0, 'jit_emit': 20.0}
    assert got['device_ops'][0] == ['jit_resolve', 40.0]
    # gaps: 20-30 (no host event), 40-60 (PjitFunction), 80-100 (tail)
    assert got['idle_gaps'] == [['PjitFunction(emit)', 20.0],
                                ['tail', 20.0],
                                ['no host event', 10.0]]


def test_no_device_events_reads_nothing():
    assert profile.reduce([_ev(HOST, 'python', 'x', 0, 5)]) is None


def test_modules_line_stands_in_for_ops():
    events = [_ev(DEV, 'XLA Modules', 'jit_a(1)', 0, 2e9),
              _ev(DEV, 'XLA Modules', 'jit_a(2)', 1e9, 2e9)]
    got = profile.reduce(events)
    assert got['busy_s'] == pytest.approx(3.0)
    assert got['modules'] == {'jit_a': 4.0}


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), 'data',
                                         'trace_*.json.gz')))


@pytest.mark.parametrize('path', RECORDED)
def test_recorded_trace(path):
    """A window traced on a v5e, cut to its first events: the reduction
    finds the chip, a busy share inside the span, and the numbers the
    recording was taken with."""
    with gzip.open(path, 'rt') as f:
        rec = json.load(f)
    events = [tuple(ev) for ev in rec['events']]
    got = profile.reduce(events)
    assert got['chips'] == 1
    assert 0 < got['busy_s'] <= got['span_s']
    assert got['busy_s'] == pytest.approx(rec['expect']['busy_s'])
    assert [k for k, _ in got['device_ops']] == \
        [k for k, _ in rec['expect']['device_ops']]
