"""Network generator ``flywire``: the FlyWire-statistics connectome of
``bench/netgen.py``, drawn on the device from the run's seed, and the
simulator's ``Connectome`` over its tables."""

from bench import netgen, program

generate = netgen.generate
connectome = program.connectome
