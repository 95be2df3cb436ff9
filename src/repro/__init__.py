"""repro — Higher-order Linear Attention, production-scale jax/pallas.

Importing the package changes no JAX setting.  Random draws under ``jit``
do not depend on the output sharding because ``jax_threefry_partitionable``
is on by default in the installed JAX, so the same init key gives the same
parameters on any mesh (tests/test_distributed.py).
"""
