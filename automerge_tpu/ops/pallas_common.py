"""Shared Pallas dispatch gate for the TPU kernel twins: Pallas runs on
a TPU backend unless the `AMTPU_NO_PALLAS` kill switch (re-read per
call) is set."""

import functools

import jax
from ..utils.common import env_bool


@functools.lru_cache(maxsize=1)
def on_tpu_cached():
    return jax.default_backend() == 'tpu'


def pallas_enabled():
    if env_bool('AMTPU_NO_PALLAS', False):
        return False
    return on_tpu_cached()
