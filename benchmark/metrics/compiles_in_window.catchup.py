"""Dispatch: backend compiles inside the window (JAX's monitoring
events; a persistent-cache read counts too).  There should be none."""


def read(ctx):
    return ctx['compiles']['compiles']
