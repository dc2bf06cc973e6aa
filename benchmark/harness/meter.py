"""What the program reports about itself, read around the window: JAX's
compile events, and the program's spans, counters and histograms.

The compile listener is the program's `chip_smoke.Meter`, copied.  The
spans and histograms are read as deltas between two snapshots, so the
window's numbers hold nothing of set-up.
"""


class Compiles(object):
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events (a cache hit fires a compile event too: its
    seconds are the read)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == '/jax/core/compile/backend_compile_duration':
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == '/jax/compilation_cache/cache_hits':
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def read(self):
        return {'compiles': self.compiles, 'compile_s': self.seconds,
                'cache_hits': self.cache_hits}


def telemetry_on():
    """Turns the program's spans on (traced runs only: spans cost time)."""
    from automerge_tpu import telemetry
    telemetry.enable()


def snapshot():
    """The program's spans, flat counters and pool batch seconds, as
    plain data."""
    from automerge_tpu import telemetry
    batch = telemetry.BATCH_LATENCY.labels('native')
    _c, b_sum, b_n = batch.read()
    return {'spans': telemetry.phase_snapshot(),
            'counters': telemetry.metrics_snapshot(),
            'pool_batch': {'s': b_sum, 'n': b_n}}


def delta(a, b):
    """`b` minus `a`, field by field."""
    spans = {}
    for k, v in b['spans'].items():
        w = a['spans'].get(k, {'s': 0.0, 'n': 0})
        if v['n'] - w['n'] or v['s'] - w['s']:
            spans[k] = {'s': v['s'] - w['s'], 'n': v['n'] - w['n']}
    counters = {k: v - a['counters'].get(k, 0.0)
                for k, v in b['counters'].items()
                if v - a['counters'].get(k, 0.0)}
    return {'spans': spans, 'counters': counters,
            'pool_batch': {'s': b['pool_batch']['s'] - a['pool_batch']['s'],
                           'n': b['pool_batch']['n'] - a['pool_batch']['n']}}
