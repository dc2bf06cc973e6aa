from harness import roofline
from doctypes import map as map_doc
from doctypes import text


def test_bytes_per_kind():
    assert roofline.resolver_bytes({'set': 2, 'del': 1, 'ins': 3,
                                    'link': 1, 'makeText': 1}) \
        == 28 * 7 + 8


def test_unknown_action_is_an_error():
    try:
        roofline.resolver_bytes({'inc': 1})
    except KeyError:
        return
    raise AssertionError('an unknown action was counted')


TEXT = {'actors_per_doc': 16, 'ops_per_change': 6, 'delete_share': 0.15}
MAP = {'actors_per_doc': 8, 'ops_per_change': 16, 'key_space': 32,
       'delete_share': 0.1, 'timestamp_share': 0.1, 'seqs_per_round': 8}


def test_counts_follow_the_rounds():
    counts = roofline.count_ops(text.round_changes(TEXT, 5, 3, 1), {})
    assert sum(counts.values()) == 100
    assert counts['ins'] == 48 + 1 and counts['makeText'] == 1
    assert counts['set'] + counts['del'] == 48 + 1
    counts = roofline.count_ops(map_doc.round_changes(MAP, 5, 3, 2), {})
    assert sum(counts.values()) == 1024


def test_the_seed_picks_the_structure():
    """Two seeds give a doc the same number of ops and different choices
    of which inserts delete and which keys a change touches."""
    def structure(mod, cfg, seed):
        return [[(op['action'], op.get('key')) for op in ch['ops']]
                for ch in mod.round_changes(cfg, seed, 3, 2)]
    for mod, cfg in ((text, TEXT), (map_doc, MAP)):
        a, b = structure(mod, cfg, 5), structure(mod, cfg, 5 + 26)
        assert a != b
        assert [len(ch) for ch in a] == [len(ch) for ch in b]
