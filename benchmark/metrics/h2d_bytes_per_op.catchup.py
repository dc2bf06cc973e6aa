"""Dispatch: `transfer.h2d_bytes` (bytes of host arrays the program
handed to the device: explicit uploads and kernel-call columns) in the
window per op it completed (program counter).  Nothing to read where the
program has no such counter."""


def read(ctx):
    b = ctx['program']['counters'].get('transfer.h2d_bytes')
    if b is None or not ctx['client']['ops_done']:
        return None
    return b / ctx['client']['ops_done']
