"""The least HBM traffic the resolution of a batch of ops requires, from
the ops' counts by kind and the columns the resolution must read and
write.  It is computed from the work, not from any implementation's
padded shapes, so it reads the same whatever resolves the ops.

Per op, in 4-byte columns:

  register op (set, del, link)   reads group, Lamport time, actor, seq,
                                 flags; writes winner/alive, conflicts
                                 -> 7 columns, 28 bytes
  list insert (ins)              linearization reads parent elem, elem
                                 counter, actor and writes a rank (4);
                                 visibility reads rank and delta and
                                 writes an index (3) -> 7 columns, 28 bytes
  object creation (make*)        reads the object id, writes its slot
                                 -> 2 columns, 8 bytes

An insert's value arrives as its own `set` and is counted there.
"""

BYTES_PER_OP = {'set': 28, 'del': 28, 'link': 28, 'ins': 28}
MAKE_BYTES = 8


def resolver_bytes(op_counts):
    """Bytes for ``{action: count}``; unknown `make*` actions count as
    creations, any other unknown action is an error."""
    total = 0
    for action, n in op_counts.items():
        if action in BYTES_PER_OP:
            total += BYTES_PER_OP[action] * n
        elif action.startswith('make'):
            total += MAKE_BYTES * n
        else:
            raise KeyError('no byte count for op action %r' % action)
    return total


def count_ops(changes, into):
    """Adds the ops of `changes` to ``into[action]``."""
    for ch in changes:
        for op in ch['ops']:
            a = op['action']
            into[a] = into.get(a, 0) + 1
    return into
