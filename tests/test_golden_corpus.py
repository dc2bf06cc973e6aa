"""Replays the golden corpus mechanically derived from the reference's own
backend test fixtures (`/root/reference/test/backend_test.js`, extracted
by tools/extract_golden_corpus.py) against every backend: the scalar
oracle, the native pool on the CPU default and on the chip's kernel
path, and the sidecar protocol surface.

The expected patches are the reference suite's own assertions -- this is
the differential-testing seam SURVEY.md section 4 calls for: hand-built
change JSON in, byte-identical patch JSON out.
"""

import json
import os
import re

import pytest

from automerge_tpu import backend as Backend
from automerge_tpu.native import NativeDocPool
from automerge_tpu.sidecar.server import SidecarBackend
from tests.conftest import lane_env

CORPUS = os.path.join(os.path.dirname(__file__), 'golden',
                      'backend_corpus.json')
with open(CORPUS) as f:
    _corpus = json.load(f)
CASES = _corpus['cases']


def case_ids():
    return [c['name'].replace(' ', '-') for c in CASES]


def run_against_oracle(case):
    state = Backend.init()
    for step in case['steps']:
        if step['op'] == 'apply_changes':
            state, patch = Backend.apply_changes(state, step['changes'])
        elif step['op'] == 'apply_local_change':
            state, patch = Backend.apply_local_change(state,
                                                      dict(step['request']))
        elif step['op'] == 'apply_local_change_error':
            with pytest.raises(Exception, match=step['error_match']):
                Backend.apply_local_change(state, dict(step['request']))
            continue
        elif step['op'] == 'get_patch':
            patch = Backend.get_patch(state)
        if 'expected' in step:
            assert patch == step['expected'], step['op']


def run_against_pool(case, pool, doc='d'):
    for step in case['steps']:
        if step['op'] == 'apply_changes':
            patch = pool.apply_changes(doc, step['changes'])
        elif step['op'] == 'apply_local_change':
            patch = pool.apply_local_change(doc, dict(step['request']))
        elif step['op'] == 'apply_local_change_error':
            with pytest.raises(Exception, match=step['error_match']):
                pool.apply_local_change(doc, dict(step['request']))
            continue
        elif step['op'] == 'get_patch':
            patch = pool.get_patch(doc)
        if 'expected' in step:
            assert patch == step['expected'], step['op']


def run_against_sidecar(case, backend, doc='d'):
    rid = [0]

    def call(cmd, **kw):
        rid[0] += 1
        return backend.handle(dict(kw, id=rid[0], cmd=cmd, doc=doc))

    for step in case['steps']:
        if step['op'] == 'apply_changes':
            resp = call('apply_changes', changes=step['changes'])
        elif step['op'] == 'apply_local_change':
            resp = call('apply_local_change', request=dict(step['request']))
        elif step['op'] == 'apply_local_change_error':
            resp = call('apply_local_change', request=dict(step['request']))
            assert 'error' in resp
            assert re.search(step['error_match'], resp['error'])
            continue
        elif step['op'] == 'get_patch':
            resp = call('get_patch')
        assert 'error' not in resp, resp
        if 'expected' in step:
            assert resp['result'] == step['expected'], step['op']


@pytest.mark.parametrize('case', CASES, ids=case_ids())
def test_oracle_matches_reference_fixtures(case):
    run_against_oracle(case)


@pytest.mark.parametrize('case', CASES, ids=case_ids())
def test_native_pool_matches_reference_fixtures(case):
    run_against_pool(case, NativeDocPool())


@pytest.mark.parametrize('case', CASES, ids=case_ids())
def test_kernel_path_matches_reference_fixtures(case):
    with lane_env('kernel'):
        run_against_pool(case, NativeDocPool())


@pytest.mark.parametrize('case', CASES, ids=case_ids())
def test_sidecar_matches_reference_fixtures(case):
    run_against_sidecar(case, SidecarBackend())


def test_corpus_covers_the_reference_suite():
    """The corpus must track the reference file: every it-block is either
    extracted or explicitly skipped with a reason."""
    ref = '/root/reference/test/backend_test.js'
    if not os.path.exists(ref):
        pytest.skip('reference suite %s not present on this host; the '
                    'committed corpus is still replayed by the fixture '
                    'tests above' % ref)
    src = open(ref).read()
    its = re.findall(r"\bit\('([^']+)'", src)
    covered = {c['name'] for c in CASES} | \
        {s['name'] for s in _corpus['skipped']}
    assert set(its) == covered
    assert len(CASES) >= 18
