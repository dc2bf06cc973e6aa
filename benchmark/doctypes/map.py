"""Map documents: concurrent key assignments by several actors on the
root map, in the Automerge 0.x wire format.  A pure function of the
configuration, the seed and the doc's index, like `text`: the seed picks
which keys a change assigns, deletes or stamps, and the values.

The shape follows the program's `bench.build_config_2` (copied here so
the yardstick cannot move): each change assigns ``ops_per_change``
distinct keys drawn from ``key_space``; an assignment is a `del` with
``delete_share`` probability, else a `set` of a number made from the
seed, the seq and the actor, typed `timestamp` with ``timestamp_share``
probability.  Changes carry no deps, so every actor's changes are
concurrent with every other's.  One round is ``seqs_per_round``
changes per actor; later rounds continue the seqs.
"""

import random

from .common import ROOT_ID


def doc_id(i):
    return 'map-%05d' % i


def round_changes(cfg, seed, i, r):
    """Round `r` (1-based) of doc `i`."""
    rng = random.Random('map:%d:%d:%d' % (seed, i, r))
    base = seed % 1000003 * 1000
    per_round = cfg['seqs_per_round']
    changes = []
    for seq in range((r - 1) * per_round + 1, r * per_round + 1):
        for a in range(cfg['actors_per_doc']):
            ops = []
            for key_n in rng.sample(range(cfg['key_space']),
                                    cfg['ops_per_change']):
                key = 'k%d' % key_n
                if rng.random() < cfg['delete_share']:
                    ops.append({'action': 'del', 'obj': ROOT_ID,
                                'key': key})
                elif rng.random() < cfg['timestamp_share']:
                    ops.append({'action': 'set', 'obj': ROOT_ID,
                                'key': key, 'value': base + seq * 10 + a,
                                'datatype': 'timestamp'})
                else:
                    ops.append({'action': 'set', 'obj': ROOT_ID,
                                'key': key, 'value': base + seq * 10 + a})
            changes.append({'actor': 'a%d' % a, 'seq': seq, 'deps': {},
                            'ops': ops})
    return changes
