"""Text documents: concurrent interleaved insert/delete by many actors,
in the Automerge 0.x wire format.  Everything is a pure function of the
configuration, the seed and the doc's index, so any process can make
any round of any doc without the rounds before it.  The seed picks which
inserts delete and the letters.

Rounds follow the shape of the program's
`parallel/mesh_encode.text_doc_changes` (copied here so the yardstick
cannot move): round 1 creates the doc (4 ops) and gives each actor one
change; every round gives each actor one change of
``ops_per_change / 2`` inserts after its own previous element, each
followed by a `set` of the new element or, with ``delete_share``
probability, a `del` of the actor's previous element.  Changes depend on
the creation change only, so every actor's changes are concurrent with
every other's.
"""

import random

from .common import ROOT_ID


def doc_id(i):
    return 'text-%05d' % i


def _obj(i):
    return 'text-obj-%05d' % i


def _letter(seed, i, elem):
    return chr(97 + (seed + 7 * i + elem) % 26)


def round_changes(cfg, seed, i, r):
    """Round `r` (1-based) of doc `i`: one change per actor."""
    n_actors = cfg['actors_per_doc']
    per = cfg['ops_per_change'] // 2
    p_del = cfg['delete_share']
    tid = _obj(i)
    rng = random.Random('text:%d:%d:%d' % (seed, i, r))
    changes = []
    if r == 1:
        changes.append({'actor': 'a0', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'makeText', 'obj': tid},
            {'action': 'ins', 'obj': tid, 'key': '_head', 'elem': 1},
            {'action': 'set', 'obj': tid, 'key': 'a0:1',
             'value': _letter(seed, i, 1)},
            {'action': 'link', 'obj': ROOT_ID, 'key': 'text',
             'value': tid}]})
    max_elem = 1 + (r - 1) * n_actors * per
    for a in range(n_actors):
        actor = 'a%d' % a
        last = None if r == 1 else '%s:%d' % (
            actor, 1 + (r - 2) * n_actors * per + (a + 1) * per)
        ops = []
        for _k in range(per):
            max_elem += 1
            ops.append({'action': 'ins', 'obj': tid, 'key': last or 'a0:1',
                        'elem': max_elem})
            delete = rng.random() < p_del
            if delete and last is not None:
                ops.append({'action': 'del', 'obj': tid, 'key': last})
            else:
                ops.append({'action': 'set', 'obj': tid,
                            'key': '%s:%d' % (actor, max_elem),
                            'value': _letter(seed, i, max_elem)})
            last = '%s:%d' % (actor, max_elem)
        changes.append({'actor': actor, 'seq': r + 1 if a == 0 else r,
                        'deps': {'a0': 1}, 'ops': ops})
    return changes
