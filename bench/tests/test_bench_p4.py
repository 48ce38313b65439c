"""The partitioned cell on 4 virtual CPU devices (shard_map over a mesh of
4): correct when sound, not correct as its control or with the exchange
between chips left out."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _child(mode: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "p4_child.py"),
                        mode], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["devices"] == 4
    return r


def test_partitioned_cell_sound_and_control():
    r = _child("sound")
    assert r["sound"]["count"] == 4
    assert r["sound"]["correct"] is True, r["sound"]["checks"]
    assert r["control"]["correct"] is False


def test_exchange_between_chips_left_out():
    r = _child("no_exchange")
    assert r["no_exchange"]["correct"] is False
