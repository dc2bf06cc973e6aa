"""Dispatch: `transfer.d2h_bytes` (bytes of device arrays the program
read back to the host: packed register words, conflict rows, ranks,
dominance indexes) in the window per op it completed (program counter).
Nothing to read where the program has no such counter."""


def read(ctx):
    b = ctx['program']['counters'].get('transfer.d2h_bytes')
    if b is None or not ctx['client']['ops_done']:
        return None
    return b / ctx['client']['ops_done']
