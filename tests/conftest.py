"""Test configuration: force JAX onto a virtual 8-device CPU mesh so the
multi-chip sharding paths (dp/sp over a Mesh) are exercised without TPU
hardware, per the build contract."""

import os
import re
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'
# force 8 virtual devices even if the env presets a different count
flags = re.sub(r'--xla_force_host_platform_device_count=\d+', '',
               os.environ.get('XLA_FLAGS', ''))
os.environ['XLA_FLAGS'] = (
    flags + ' --xla_force_host_platform_device_count=8').strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
