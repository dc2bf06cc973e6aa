"""Seconds from process start until the window opens: building,
loading, generating ahead, warming and compiling."""


def read(ctx):
    return ctx['setup_s']
